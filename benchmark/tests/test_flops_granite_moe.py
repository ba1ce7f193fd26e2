"""``flops_granite_moe.py`` against counts made by hand for the
configuration in the benchmark (granite-4.0-h-small: the first period of 40
layers, 9 Mamba-2 layers and 1 attention layer, in each 9 of 72 experts of
768 at 10 a token beside a shared SwiGLU of 1536, an eighth of the
vocabulary, one sequence of 16384)."""

import os

import flops_granite_moe as counts
import harness

D, V, S = 4096, 12544, 16384
DI, CONV, MH, N = 8192, 8448, 128, 128
HEADS, KV, HD = 32, 8, 128
F, FS, E, HELD, K = 768, 1536, 72, 9, 10
PEAK, BANDWIDTH = 197e12, 819e9


def config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "granitemoehybrid_moe":
            return held
    raise AssertionError("no granitemoehybrid_moe configuration")


def test_layers_and_parameters():
    held = config()
    assert counts.layer_counts(held) == {"mamba": 9, "attention": 1}
    assert (counts.d_inner(held), counts.conv_dim(held)) == (DI, CONV)
    mamba = D * (DI + CONV + MH) + DI * D
    assert mamba == 102_236_160 == counts.mamba_params(held)  # 68.7 + 33.6
    attention = 2 * D * HD * (HEADS + KV)
    assert attention == 41_943_040 == counts.attention_params(held)
    expert = 3 * D * F
    assert expert == 9_437_184 == counts.expert_params(held)
    assert counts.mlp_params(held) == 3 * D * FS == 18_874_368
    assert counts.router_width(held) == E
    assert counts.held_share(held) == 0.125
    # The issue's count: a state-space layer at 9 held experts 206.4 M, the
    # attention layer 146.0 M, the table 51.4 M; 72 experts 679.5 M a layer.
    ffn_held = 3 * D * FS + D * E + HELD * expert
    assert round((mamba + ffn_held) / 1e6, 1) == 206.3
    assert round((attention + ffn_held) / 1e6, 1) == 146.0
    assert round(E * expert / 1e6, 1) == 679.5
    assert round(D * V / 1e6, 1) == 51.4
    # To the parameter what the program's init makes (tests/
    # test_granite_moe.py): 2.055 B held, 32.2 B published, 9 B a token.
    assert counts.held_params(held) == 2_055_031_424
    assert counts.published_params(held) == 32_207_337_984
    active = 9 * mamba + attention + 10 * (
        3 * D * FS + D * E + K * expert * 0.125) + D * V
    assert counts.active_matmul_params(held) == active == 1_323_106_304
    assert round(counts.active_matmul_params(held, published=True) / 1e9,
                 2) == 8.80
    # A token's routed experts here are 1.25 of 10: 9 % of the matmul
    # parameters it goes through on this chip, 43 % in the whole model.
    assert round(10 * K * expert * 0.125 / active, 2) == 0.09
    assert round(40 * K * expert
                 / counts.active_matmul_params(held, True), 2) == 0.43


def test_model_flops_per_token():
    held = config()
    scan = 15.0 * DI * N
    assert counts.scan_flops_per_token(held) == scan == 15_728_640.0
    want = 6.0 * 1_323_106_304 + 12.0 * 1 * D * S + 9 * scan
    assert counts.model_flops_per_token(held, S) == want
    assert round(want / 1e9, 3) == 8.886
    # Attention over 16384 keys is 9 % of it, the nine scans under 2 %.
    assert round(12.0 * D * S / want, 2) == 0.09
    assert round(9 * scan / want, 3) == 0.016


def test_step_kernel_calls():
    """Every Mosaic kernel the step runs, the gate-norm's pair included;
    ``tests/test_chip_compile_granite_moe.py`` holds the calls to the traced
    step's census."""
    held = config()
    calls = counts.step_kernel_calls(held, 1, S, 512, 512, True)
    assert {name: one["calls"] for name, one in calls.items()} == {
        "ssd_fwd": 18, "ssd_bwd": 9, "conv_silu_fwd": 18, "conv_silu_bwd": 9,
        "gated_norm_fwd": 18, "gated_norm_bwd": 9,
        # 16384 keys at heads of 128 are worth keeping: once.
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
        "gmm": 90, "tgmm": 30, "moe_rows_to_tokens": 20}
    without = counts.step_kernel_calls(held, 1, S, 512, 512, False)
    assert without["ssd_fwd"]["calls"] == 9 == without["conv_silu_fwd"][
        "calls"] and without["gmm"]["calls"] == 90
    # A grouped product at the even share: 20,480 rows of 4096 x 768.
    rows = S * K // 8
    assert rows == 20480
    gmm = calls["gmm"]
    assert gmm["flops"] == 2.0 * rows * D * F == calls["tgmm"]["flops"]
    assert gmm["bytes"] == rows * (D + F) * 2 + HELD * D * F * 2
    # ... and at what the counters measured.
    more = counts.step_kernel_calls(held, 1, S, 512, 512, True, 0.2)
    assert more["gmm"]["flops"] == 2.0 * S * K * 0.2 * D * F
    back = calls["moe_rows_to_tokens"]
    assert back["flops"] == 0.0 and back["bytes"] == rows * D * 2 + S * D * 4
    # The row passes move whole arrays and multiply nothing.
    assert calls["conv_silu_fwd"] == {"flops": 0.0, "calls": 18,
                                      "bytes": 2.0 * S * CONV * 2}
    assert calls["conv_silu_bwd"]["bytes"] == 3.0 * S * CONV * 2
    assert calls["gated_norm_fwd"]["bytes"] == 3.0 * S * DI * 2
    assert calls["gated_norm_bwd"]["bytes"] == 5.0 * S * DI * 2
    # The scan at 128 heads: twice granite-4.0-h-micro's per token.
    chunks, L, P = S // 256, 256, 64
    forward = chunks * (2.0 * L * L * N + MH * (2.0 * L * L * P
                                                + 2 * 2.0 * L * N * P))
    assert calls["ssd_fwd"]["flops"] == forward
    assert calls["ssd_bwd"]["flops"] == chunks * (
        3 * 2.0 * L * L * N + MH * (2 * 2.0 * L * L * P
                                    + 5 * 2.0 * L * N * P))
    # The step's Mosaic work is compute-bound in the products and
    # bytes-bound in the passes: under a second of the chip at its peaks.
    least = sum(one["calls"] * counts.least_seconds(one, PEAK, BANDWIDTH)
                for one in calls.values())
    assert 0.1 < least < 1.0
