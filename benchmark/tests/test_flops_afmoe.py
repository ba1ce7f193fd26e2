"""``flops_afmoe.py`` against counts made by hand for the configuration in
the benchmark (Trinity-Large-Preview, one chip of the 32 that share a layer:
1 dense + 4 expert layers, 8 of 256 experts, 25024 of the vocabulary, one
sequence of 16384)."""

import os

import flops_afmoe
import harness

D, HEADS, KV, HD, F, FE, V, S, W = 3072, 48, 8, 128, 12288, 3072, 25024, \
    16384, 4096


def config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "afmoe":
            return held
    raise AssertionError("no afmoe configuration")


def test_layers_and_parameters():
    held = config()
    assert flops_afmoe.layer_counts(held) == {
        "dense": 1, "moe": 4, "sliding": 4, "full": 1}
    assert flops_afmoe.layer_kinds(held) == [
        (True, True), (False, True), (False, True), (False, False),
        (False, True)]
    attention = 3 * D * HEADS * HD + 2 * D * KV * HD
    assert attention == 62_914_560 == flops_afmoe.attention_params(held)
    expert = 3 * D * FE
    assert expert == 28_311_552 == flops_afmoe.expert_params(held)
    assert flops_afmoe.held_share(held) == 8 / 256
    assert flops_afmoe.router_width(held) == 256
    dense_layer = attention + 3 * D * F
    assert dense_layer == 176_160_768
    # An expert layer on this chip: the router at its whole width, the
    # shared expert, and 4 x 8 / 256 = 0.125 routed experts a token.
    active_layer = attention + D * 256 + expert * (1 + 0.125)
    active = dense_layer + 4 * active_layer + D * V
    assert flops_afmoe.active_matmul_params(held) == active == 635_240_448
    held_layer = attention + D * 256 + expert * (1 + 8)
    assert held_layer == 318_504_960
    assert flops_afmoe.held_params(held) == \
        dense_layer + 4 * held_layer + 2 * D * V == 1_603_928_064
    # The whole published model by the same count: 398.6 B.
    whole = dict(held, num_hidden_layers=60, num_dense_layers=6,
                 num_experts=256, vocab_size=200192, deployment={})
    assert 398.5e9 < flops_afmoe.held_params(whole) < 398.7e9
    assert flops_afmoe.held_share(whole) == 1.0


def test_model_flops_per_token():
    held = config()
    # A window layer's scores over min(S, window) keys, a full layer's
    # over S.
    attention = 12 * HEADS * HD * (S + 4 * W)
    want = 6 * 635_240_448 + attention
    assert flops_afmoe.model_flops_per_token(held, S) == want \
        == 6_227_361_792
    short = flops_afmoe.model_flops_per_token(held, 2048)
    assert short == 6 * 635_240_448 + 12 * HEADS * HD * 5 * 2048
    # The head is 12 % of the active matmul parameters.
    assert 0.12 < D * V / 635_240_448 < 0.125


def test_executed_tiles():
    assert flops_afmoe.executed_tiles(S, None, 512, 512) == 528
    assert flops_afmoe.executed_tiles(S, W, 512, 512) == 252
    assert flops_afmoe.executed_tiles(32768, W, 512, 512) == 540
    assert flops_afmoe.executed_tiles(32768, None, 512, 512) == 2080
    assert flops_afmoe.executed_tiles(S, S, 512, 512) == 528
    # One key: the diagonal's tiles alone.
    assert flops_afmoe.executed_tiles(2048, 1, 512, 256) == 8


def test_step_kernel_calls():
    held = config()
    calls = flops_afmoe.step_kernel_calls(held, 1, S, 512, 512, True)
    assert {k: v["calls"] for k, v in calls.items()} == {
        "flash_fwd_win": 4, "flash_fwd": 1, "flash_bwd_dq_win": 4,
        "flash_bwd_dq": 1, "flash_bwd_dkv_win": 4, "flash_bwd_dkv": 1,
        "gmm": 36, "tgmm": 12}
    once = flops_afmoe.step_kernel_calls(held, 1, S, 512, 512, False)
    assert once["gmm"]["calls"] == 24 and once["flash_fwd"]["calls"] == 1
    tile = 2 * 512 * 512 * HD
    assert calls["flash_fwd_win"]["flops"] == HEADS * 252 * 2 * tile
    assert calls["flash_fwd"]["flops"] == HEADS * 528 * 2 * tile
    assert calls["flash_bwd_dq_win"]["flops"] == HEADS * 252 * 3 * tile
    assert calls["flash_bwd_dkv_win"]["flops"] == HEADS * 252 * 4 * tile
    rows = HEADS * S * 2
    assert calls["flash_fwd_win"]["bytes"] == rows * 4 * HD
    assert calls["flash_bwd_dkv"]["bytes"] == rows * 6 * HD
    # 2,048 rows under even routing; twice that where the counters say so.
    assert calls["gmm"]["flops"] == 2 * 2048 * D * FE
    assert calls["gmm"]["bytes"] == 2048 * (D + FE) * 2 + 8 * D * FE * 2
    twice = flops_afmoe.step_kernel_calls(held, 1, S, 512, 512, True,
                                          2 / 32)
    assert twice["tgmm"]["flops"] == 2 * calls["tgmm"]["flops"]
    # A sequence the window holds whole runs the causal kernels alone.
    short = flops_afmoe.step_kernel_calls(held, 1, 4096, 512, 512, True)
    assert short["flash_fwd"]["calls"] == 5 and "flash_fwd_win" not in short
    # Compute bounds the flash kernels on a v5e, the weights' bytes a
    # grouped product at this share.
    fwd, gmm = calls["flash_fwd_win"], calls["gmm"]
    assert fwd["flops"] / 197e12 > fwd["bytes"] / 819e9
    assert gmm["flops"] / 197e12 < gmm["bytes"] / 819e9
    assert flops_afmoe.least_seconds(gmm, 197e12, 819e9) == \
        gmm["bytes"] / 819e9
