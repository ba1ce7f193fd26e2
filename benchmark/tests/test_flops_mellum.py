"""``flops_mellum.py`` against counts made by hand for the configuration in
the benchmark (Mellum2-12B-A2.5B, 4 of 28 layers: three window layers of
1024 and one full layer, all 64 experts of 896 at 8 a token and the whole
vocabulary of 98304, one sequence of 16384)."""

import os

import flops_mellum
import harness

D, HEADS, KV, HD, FE, E, K, V, S, W = 2304, 32, 4, 128, 896, 64, 8, 98304, \
    16384, 1024


def config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "mellum":
            return held
    raise AssertionError("no mellum configuration")


def test_layers_and_parameters():
    held = config()
    assert flops_mellum.layer_counts(held) == {
        "layers": 4, "sliding": 3, "full": 1}
    attention = 2 * D * HEADS * HD + 2 * D * KV * HD
    assert attention == 21_233_664 == flops_mellum.attention_params(held)
    expert = 3 * D * FE
    assert expert == 6_193_152 == flops_mellum.expert_params(held)
    assert flops_mellum.held_share(held) == 1.0
    assert flops_mellum.router_width(held) == E
    # ISSUE 58's count: 21.2 M attention, 0.147 M router, 396.4 M in the
    # 64 experts, and the four norm vectors.
    a_layer = attention + D * E + E * expert + 2 * D + 2 * HD
    assert a_layer == 417_747_712 == flops_mellum.layer_params(held)
    assert flops_mellum.held_params(held) == 4 * a_layer + 2 * D * V + D \
        == 2_123_977_984
    active_layer = attention + D * E + K * expert
    assert active_layer == 70_926_336
    assert flops_mellum.active_matmul_params(held) \
        == 4 * active_layer + D * V == 510_197_760
    # The whole published model by the same count: 12.15 B, 2.44 B active.
    whole = dict(held, num_hidden_layers=28)
    assert flops_mellum.held_params(whole) == 28 * a_layer + 2 * D * V + D
    assert 12.14e9 < flops_mellum.held_params(whole) < 12.16e9
    assert 2.43e9 < flops_mellum.active_matmul_params(whole) + D * V \
        < 2.45e9
    # A share of the experts counts what this chip computes.
    shared = dict(held, num_experts=16, deployment={"experts_held": {
        "first": 0, "count": 16, "of": 64}})
    assert flops_mellum.active_matmul_params(shared) == \
        4 * (attention + D * E + K * expert / 4) + D * V


def test_model_flops_per_token():
    held = config()
    # A window layer's scores over min(S, window) keys, the full layer's
    # over S.
    attention = 12 * HEADS * HD * (S + 3 * W)
    assert attention == 956_301_312 \
        == flops_mellum.attention_flops_per_token(held, S)
    want = 6 * 510_197_760 + attention
    assert flops_mellum.model_flops_per_token(held, S) == want \
        == 4_017_487_872
    # ISSUE 58's parts of the 4.02 GFLOP: the layers' active parameters
    # 1.70 (the eight experts 1.19), scores and values 0.96 (the full layer
    # 0.81), the head 1.36: a third of the count at four layers.
    assert round(6 * 4 * 70_926_336 / 1e9, 2) == 1.70
    assert round(6 * 4 * K * 6_193_152 / 1e9, 2) == 1.19
    assert round(12 * HEADS * HD * S / 1e9, 2) == 0.81
    assert round(6 * D * V / 1e9, 2) == 1.36
    assert 0.33 < 6 * D * V / want < 0.34
    short = flops_mellum.model_flops_per_token(held, 512)
    assert short == 6 * 510_197_760 + 12 * HEADS * HD * 4 * 512


def test_executed_tiles_of_the_window():
    # A row of tiles of 512 is three, two of them cut; of 256 five, two cut.
    assert flops_mellum.executed_tiles(S, W, 512, 512) == 1 + 2 + 3 * 30 == 93
    assert flops_mellum.executed_tiles(S, W, 256, 256) == 10 + 5 * 60 == 310
    assert flops_mellum.executed_tiles(S, None, 512, 512) == 528
    assert flops_mellum.executed_tiles(S, None, 256, 256) == 2080
    pairs = W * (W + 1) // 2 + (S - W) * W
    assert round(pairs / (93 * 512 * 512), 3) == 0.667
    assert round(pairs / (310 * 256 * 256), 3) == 0.800


def test_step_kernel_calls():
    held = config()
    calls = flops_mellum.step_kernel_calls(held, 1, S, 512, 512, True)
    # A window of 1024 is under 32 keys a dimension of a head of 128: the
    # rematerialised block runs the window layers' forward kernel again;
    # the full layer's outputs are kept.
    assert not flops_mellum.keeps_forward(W, HD)
    assert flops_mellum.keeps_forward(4096, HD)
    assert {k: v["calls"] for k, v in calls.items()} == {
        "flash_fwd_win": 6, "flash_fwd": 1, "flash_bwd_dq_win": 3,
        "flash_bwd_dq": 1, "flash_bwd_dkv_win": 3, "flash_bwd_dkv": 1,
        "gmm": 36, "tgmm": 12}
    once = flops_mellum.step_kernel_calls(held, 1, S, 512, 512, False)
    assert once["gmm"]["calls"] == 24 and once["flash_fwd"]["calls"] == 1
    assert once["flash_fwd_win"]["calls"] == 3
    tile = 2 * 512 * 512 * HD
    assert calls["flash_fwd_win"]["flops"] == HEADS * 93 * 2 * tile
    assert calls["flash_fwd"]["flops"] == HEADS * 528 * 2 * tile
    assert calls["flash_bwd_dq_win"]["flops"] == HEADS * 93 * 3 * tile
    assert calls["flash_bwd_dkv_win"]["flops"] == HEADS * 93 * 4 * tile
    small = flops_mellum.step_kernel_calls(held, 1, S, 256, 256, True)
    assert small["flash_fwd_win"]["flops"] == \
        HEADS * 310 * 2 * (2 * 256 * 256 * HD)
    # Every query head against its own copy of K and V.
    rows = HEADS * S * 2
    assert calls["flash_fwd_win"]["bytes"] == rows * 4 * HD
    assert calls["flash_bwd_dkv"]["bytes"] == rows * 6 * HD
    # 131,072 rows whatever the router decides: every expert is held.
    assert calls["gmm"]["flops"] == 2 * S * K * D * FE == 541_165_879_296
    assert calls["gmm"]["bytes"] == S * K * (D + FE) * 2 + E * D * FE * 2
    assert calls["tgmm"]["flops"] == calls["gmm"]["flops"]
    # A sequence the window holds whole runs the causal kernels alone.
    short = flops_mellum.step_kernel_calls(held, 1, 1024, 512, 512, True)
    assert short["flash_fwd"]["calls"] == 8 and "flash_fwd_win" not in short
    # Compute bounds every kernel of the step on a v5e.
    for call in calls.values():
        assert call["flops"] / 197e12 > call["bytes"] / 819e9
        assert flops_mellum.least_seconds(call, 197e12, 819e9) == \
            call["flops"] / 197e12
