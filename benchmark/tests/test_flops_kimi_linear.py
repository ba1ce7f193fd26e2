"""``flops_kimi_linear.py`` against counts made by hand for the configuration
in the benchmark (Kimi-Linear-48B-A3B-Instruct, one chip of the 8 that share
a layer: published layers 1-5, 32 of 256 experts, 20480 of the vocabulary,
one sequence of 16384)."""

import os

import flops_kimi_linear
import harness

D, KH, HD, TAPS, H, F, FE, V, S = 2304, 32, 128, 4, 32, 9216, 1024, 20480, \
    16384


def config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "kimi_linear":
            return held
    raise AssertionError("no kimi_linear configuration")


def test_layers_and_parameters():
    held = config()
    assert flops_kimi_linear.layer_counts(held) == {
        "dense": 1, "moe": 4, "kda": 4, "mla": 1}
    assert flops_kimi_linear.layer_kinds(held) == [
        (True, True), (False, True), (False, True), (False, False),
        (False, True)]
    assert flops_kimi_linear.longest_kda_run(held) == 2
    wide = KH * HD
    kda = (3 * D * wide + 3 * TAPS * wide + 2 * (D * HD + HD * wide)
           + D * KH + wide * D)
    assert kda == 39_510_016 == flops_kimi_linear.kda_params(held)
    mla = D * H * 192 + D * (512 + 64) + 512 * H * 256 + H * 128 * D
    assert mla == 29_114_368
    expert = 3 * D * FE
    assert expert == 7_077_888 == flops_kimi_linear.expert_params(held)
    assert flops_kimi_linear.held_share(held) == 32 / 256
    assert flops_kimi_linear.router_width(held) == 256
    # An expert layer on this chip: the router at its whole width, the
    # shared expert, and 8 x 32 / 256 = 1 routed expert a token.
    ffn = D * 256 + expert * (1 + 1)
    active = 4 * kda + mla + 3 * D * F + 4 * ffn + D * V
    assert flops_kimi_linear.active_matmul_params(held) == active \
        == 357_023_744
    held_ffn = D * 256 + expert * (1 + 32)
    assert flops_kimi_linear.held_params(held) == \
        4 * kda + mla + 3 * D * F + 4 * held_ffn + 2 * D * V \
        == 1_281_867_776
    # The whole published model by the same count: 49.1 B.
    whole = dict(held, num_hidden_layers=27, num_experts=256,
                 vocab_size=163840, deployment={})
    assert 49.0e9 < flops_kimi_linear.held_params(whole) < 49.2e9
    assert flops_kimi_linear.layer_counts(whole) == {
        "dense": 1, "moe": 26, "kda": 20, "mla": 7}
    assert flops_kimi_linear.held_share(whole) == 1.0


def test_model_flops_per_token():
    held = config()
    # The literal recurrence: 7 a state element forward, 21 in training.
    recurrence = 21 * KH * HD * HD
    assert flops_kimi_linear.recurrence_flops_per_token(held) == recurrence \
        == 11_010_048
    attention = 6 * H * (192 + 128) * S
    want = 6 * 357_023_744 + attention + 4 * recurrence
    assert flops_kimi_linear.model_flops_per_token(held, S) == want \
        == 3_192_815_616
    # The four KDA mixers (projections and recurrence) are 31 % of it, the
    # head 8.9 %.
    mixers = 4 * (6 * 39_510_016 + recurrence)
    assert 0.31 < mixers / want < 0.312
    assert 0.088 < 6 * D * V / want < 0.09


def test_step_kernel_calls():
    held = config()
    calls = flops_kimi_linear.step_kernel_calls(held, 1, S, 128, 512, 512,
                                                True)
    assert {k: v["calls"] for k, v in calls.items()} == {
        "kda_fwd": 8, "kda_bwd": 4, "flash_fwd": 1, "flash_bwd_dq": 1,
        "flash_bwd_dkv": 1, "gmm": 36, "tgmm": 12}
    once = flops_kimi_linear.step_kernel_calls(held, 1, S, 128, 512, 512,
                                               False)
    assert once["kda_fwd"]["calls"] == 4 and once["gmm"]["calls"] == 24
    # A chunk of 128 of one head: 7 levels of two [L, K] x [K, L] and two
    # [L, L] x [L, L] products, three products with the state, two of
    # [L, L] x [L, V].
    L = 128
    chunk = 7 * (4 * L * L * HD + 4 * L ** 3) + 6 * L * HD * HD \
        + 4 * L * L * HD
    chunks = S // L * KH
    assert calls["kda_fwd"]["flops"] == chunks * chunk
    assert calls["kda_bwd"]["flops"] == 3 * chunks * chunk
    tokens = S * KH
    states = chunks * HD * HD * 4
    assert calls["kda_fwd"]["bytes"] == \
        4 * tokens * HD * 2 + tokens * (HD + 1) * 4 + states
    assert calls["kda_bwd"]["bytes"] == \
        7 * tokens * HD * 2 + 2 * tokens * (HD + 1) * 4 + states
    # Chunks of 64 execute less and move more: twice the states.
    short = flops_kimi_linear.kda_call("kda_fwd", held, 1, S, 64)
    assert short["flops"] < calls["kda_fwd"]["flops"]
    assert short["bytes"] - calls["kda_fwd"]["bytes"] == states
    tile = 2 * 512 * 512
    assert calls["flash_fwd"]["flops"] == H * 528 * tile * (192 + 128)
    assert calls["flash_bwd_dkv"]["flops"] == H * 528 * tile * 2 * (192 + 128)
    # 16,384 rows under even routing; half that where the counters say so.
    assert calls["gmm"]["flops"] == 2 * S * D * FE
    assert calls["gmm"]["bytes"] == S * (D + FE) * 2 + 32 * D * FE * 2
    half = flops_kimi_linear.step_kernel_calls(held, 1, S, 128, 512, 512,
                                               True, 1 / 16)
    assert 2 * half["tgmm"]["flops"] == calls["tgmm"]["flops"]
    # On a v5e the count makes kda_fwd at chunks of 128 compute-bound and at
    # 64 bound by its bytes.
    fwd = calls["kda_fwd"]
    assert fwd["flops"] / 197e12 > fwd["bytes"] / 819e9
    assert short["flops"] / 197e12 < short["bytes"] / 819e9
    assert flops_kimi_linear.least_seconds(short, 197e12, 819e9) == \
        short["bytes"] / 819e9
