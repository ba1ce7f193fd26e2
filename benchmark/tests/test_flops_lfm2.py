"""``flops_lfm2.py`` against counts made by hand for the configuration in
the benchmark (LFM2-24B-A2B, one chip of the 8 that share a layer: published
layers 1-17, 8 of 64 experts, 8192 of the vocabulary, four sequences of
8192), for ISSUE 37's first choice of depth (layers 1-13) and for the whole
published model."""

import os

import flops_lfm2
import harness

D, H, KV, HD, TAPS, F, FE, V, S, B = 2048, 32, 8, 64, 3, 11776, 1536, 8192, \
    8192, 4


def config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "lfm2_moe":
            return held
    raise AssertionError("no lfm2_moe configuration")


def test_layers_and_parameters():
    held = config()
    assert flops_lfm2.layer_counts(held) == {
        "dense": 1, "moe": 16, "conv": 13, "attention": 4}
    assert flops_lfm2.layer_kinds(held)[:5] == [
        (True, True), (False, False), (False, True), (False, True),
        (False, True)]
    conv = D * 3 * D + TAPS * D + D * D
    assert conv == 16_783_360 == flops_lfm2.conv_params(held)
    attention = 2 * D * H * HD + 2 * D * KV * HD
    assert attention == 10_485_760 == flops_lfm2.attention_params(held)
    expert = 3 * D * FE
    assert expert == 9_437_184 == flops_lfm2.expert_params(held)
    assert flops_lfm2.held_share(held) == 8 / 64
    assert flops_lfm2.router_width(held) == 64
    # An expert layer on this chip: the router at its whole width and
    # 4 x 8 / 64 = half a routed expert a token; no shared expert.
    ffn = D * 64 + expert * 0.5
    active = 13 * conv + 4 * attention + 3 * D * F + 16 * ffn + D * V
    assert flops_lfm2.active_matmul_params(held) == active == 426_850_304
    held_ffn = D * 64 + expert * 8
    # The table is embedding and head: held once.
    assert flops_lfm2.held_params(held) == \
        13 * conv + 4 * attention + 3 * D * F + 16 * held_ffn + D * V \
        == 1_559_312_384
    # ISSUE 37's first choice, three periods behind the dense layer.
    three = dict(held, num_hidden_layers=13)
    assert flops_lfm2.layer_counts(three) == {
        "dense": 1, "moe": 12, "conv": 10, "attention": 3}
    assert flops_lfm2.held_params(three) == 1_195_962_368
    assert flops_lfm2.active_matmul_params(three) == 346_615_808
    # The whole published model by the same count: 23.84 B, 2.33 B a token.
    whole = dict(held, num_hidden_layers=40, num_dense_layers=2,
                 num_experts=64, vocab_size=65536, deployment={})
    assert flops_lfm2.layer_counts(whole) == {
        "dense": 2, "moe": 38, "conv": 30, "attention": 10}
    assert flops_lfm2.held_share(whole) == 1.0
    assert flops_lfm2.held_params(whole) == 23_843_491_840
    assert flops_lfm2.active_matmul_params(whole) == 2_326_712_320


def test_model_flops_per_token():
    held = config()
    attention = 12 * 4 * D * S
    want = 6 * 426_850_304 + attention
    assert flops_lfm2.model_flops_per_token(held, S) == want \
        == 3_366_408_192
    assert flops_lfm2.model_flops_per_token(
        dict(held, num_hidden_layers=13), S) == 2_683_674_624
    # The thirteen convolution mixers are 38.9 % of it by this convention
    # (attention over the full S x S: ISSUE 37's 42 % credits the causal
    # half), the four attention layers 31.4 %, the head 3.0 %.
    assert 0.388 < 78 * 16_783_360 / want < 0.389
    assert 0.313 < (24 * 10_485_760 + attention) / want < 0.314
    assert 0.029 < 6 * D * V / want < 0.030


def test_step_kernel_calls():
    held = config()
    calls = flops_lfm2.step_kernel_calls(held, B, S, 512, 512, True)
    assert {k: v["calls"] for k, v in calls.items()} == {
        "short_conv_fwd": 26, "short_conv_bwd": 13, "flash_fwd": 4,
        "flash_bwd_dq": 4, "flash_bwd_dkv": 4, "gmm": 144, "tgmm": 48}
    once = flops_lfm2.step_kernel_calls(held, B, S, 512, 512, False)
    assert once["short_conv_fwd"]["calls"] == 13 \
        and once["gmm"]["calls"] == 96
    # A forward kernel whose outputs are not kept runs twice under remat.
    plain = flops_lfm2.step_kernel_calls(held, B, S, 512, 512, True,
                                         flash_kept=False)
    assert plain["flash_fwd"]["calls"] == 8 \
        and plain["flash_bwd_dq"]["calls"] == 4
    cells = B * S * D
    assert calls["short_conv_fwd"] == {
        "calls": 26, "flops": cells * 7, "bytes": cells * 4 * 2}
    assert calls["short_conv_bwd"] == {
        "calls": 13, "flops": cells * 22, "bytes": cells * 7 * 2}
    # Bytes-bound on a v5e by two orders of magnitude.
    for kernel in ("short_conv_fwd", "short_conv_bwd"):
        one = calls[kernel]
        assert one["bytes"] / 819e9 > 50 * one["flops"] / 197e12
        assert flops_lfm2.least_seconds(one, 197e12, 819e9) == \
            one["bytes"] / 819e9
    assert abs(calls["short_conv_fwd"]["bytes"] / 819e9 - 0.655e-3) < 1e-6
    tile = 2 * 512 * 512
    tiles = 16 * 17 // 2
    assert calls["flash_fwd"]["flops"] == B * H * tiles * tile * 2 * HD
    assert calls["flash_bwd_dkv"]["flops"] == B * H * tiles * tile * 4 * HD
    assert calls["flash_fwd"]["bytes"] == B * H * S * 2 * 4 * HD
    # 16,384 rows under even routing; half that where the counters say so.
    assert calls["gmm"]["flops"] == 2 * (B * S // 2) * D * FE
    assert calls["gmm"]["bytes"] == (B * S // 2) * (D + FE) * 2 \
        + 8 * D * FE * 2
    half = flops_lfm2.step_kernel_calls(held, B, S, 512, 512, True,
                                        share=1 / 16)
    assert 2 * half["tgmm"]["flops"] == calls["tgmm"]["flops"]
