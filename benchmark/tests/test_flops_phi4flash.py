"""``flops_phi4flash.py`` against counts made by hand for the configuration
in the benchmark (Phi-4-mini-flash-reasoning, whole pairs of its three parts,
the whole vocabulary, one sequence of 16384), for every rung of ISSUE 42's
cut and for the whole published model."""

import os

import pytest

import flops_phi4flash
import harness

D, F, H, KV, HD, V, S = 2560, 10240, 40, 20, 64, 200064, 16384
DI, N, TAPS, RANK, WINDOW = 5120, 16, 4, 160, 512
RUNGS = {"4-1-3": (4, 3), "3-1-3": (3, 3), "2-1-2": (2, 2), "1-1-1": (1, 1)}


def config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "phi4flash":
            return held
    raise AssertionError("no phi4flash configuration")


def rung(n_self, n_cross):
    layers = list(range(2 * n_self)) + [16, 17] \
        + list(range(18, 18 + 2 * n_cross))
    return dict(config(), num_hidden_layers=len(layers), layers_run=layers)


def whole():
    return dict(config(), num_hidden_layers=32, layers_run=list(range(32)),
                reduced={})


def test_the_kind_of_every_published_layer():
    kinds = [flops_phi4flash.layer_kind(whole(), i) for i in range(32)]
    assert kinds[:16] == ["mamba", "window"] * 8
    assert kinds[16:18] == ["mamba", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert flops_phi4flash.layer_counts(whole()) == {
        "mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    held = config()
    assert flops_phi4flash.layers_run(held) == held["layers_run"]
    assert sum(flops_phi4flash.layer_counts(held).values()) \
        == held["num_hidden_layers"]


def test_a_layers_parameters():
    held = config()
    ffn = 3 * D * F
    assert ffn == 78_643_200
    mamba = D * 2 * DI + TAPS * DI + DI * (RANK + 2 * N) + RANK * DI + DI * D
    assert mamba == 41_144_320 == flops_phi4flash.mixer_params(held, "mamba")
    attention = 2 * D * H * HD + 2 * D * KV * HD
    assert attention == 19_660_800 \
        == flops_phi4flash.mixer_params(held, "window") \
        == flops_phi4flash.mixer_params(held, "full")
    assert flops_phi4flash.mixer_params(held, "gmu") == 2 * D * DI \
        == 26_214_400
    assert flops_phi4flash.mixer_params(held, "cross") == 2 * D * D \
        == 13_107_200
    # ISSUE 42's layers, SwiGLU and vectors in: 119.9, 98.3, 104.9, 91.8 M.
    assert flops_phi4flash.mamba_sizes(held) == {
        "d_inner": DI, "d_state": N, "d_conv": TAPS, "dt_rank": RANK}
    assert D * V == 512_163_840


@pytest.mark.parametrize("name,matmul,held_all", [
    ("4-1-3", 2_192_445_440, 2_193_157_632),
    ("3-1-3", 1_974_353_920, 1_974_940_288),
    ("2-1-2", 1_559_654_400, 1_560_088_960),
    ("1-1-1", 1_144_954_880, 1_145_237_632)])
def test_a_rungs_parameters_are_the_issues_table(name, matmul, held_all):
    cut = rung(*RUNGS[name])
    assert flops_phi4flash.matmul_params(cut) == matmul
    assert flops_phi4flash.all_params(cut) == held_all
    # ISSUE 42's table: 2.193, 1.975, 1.560 and 1.145 B.
    want = {"4-1-3": 2.193, "3-1-3": 1.975, "2-1-2": 1.560, "1-1-1": 1.145}
    assert round(held_all / 1e9, 3) == want[name]


def test_the_whole_model_is_the_published_3_85_b():
    assert flops_phi4flash.matmul_params(whole()) == 3_851_243_520
    assert flops_phi4flash.all_params(whole()) == 3_852_562_944


def test_model_flops_per_token():
    cut = rung(4, 3)
    # A query head's two products over the keys it sees: q k^T at 64 and
    # p V_g at 128, 6 a multiply-add pair in training.
    attention = 6 * H * 3 * HD * (4 * WINDOW + 4 * S)
    assert flops_phi4flash.attention_flops_per_token(cut, S) == attention \
        == 3_114_270_720
    recurrence = 18 * DI * N * 5
    assert flops_phi4flash.recurrence_flops_per_token(cut) == recurrence \
        == 7_372_800
    want = 6 * 2_192_445_440 + attention + recurrence
    assert flops_phi4flash.model_flops_per_token(cut, S) == want \
        == 16_276_316_160
    assert flops_phi4flash.model_flops_per_token(rung(3, 3), S) \
        == 14_942_699_520
    assert flops_phi4flash.model_flops_per_token(whole(), S) \
        == 29_349_273_600
    # What the cut does to the reading: the head is 18.9 % of the counted
    # FLOPs at 16 layers, 20.6 % at 14 and 10.5 % in the whole model.
    head = 6 * D * V
    assert 0.188 < head / want < 0.190
    assert 0.205 < head / 14_942_699_520 < 0.207
    assert 0.104 < head / 29_349_273_600 < 0.106
    # A window the sequence does not reach is every key before the query.
    assert flops_phi4flash.attention_flops_per_token(cut, 256) \
        == 6 * H * 3 * HD * 8 * 256


def test_the_scan_and_the_convolution_by_their_bytes():
    held = config()
    cells = S * DI
    fwd = flops_phi4flash.selective_scan_call("selective_scan_fwd", held, 1,
                                              S)
    bwd = flops_phi4flash.selective_scan_call("selective_scan_bwd", held, 1,
                                              S)
    assert fwd == {"flops": cells * N * 6.0, "bytes": cells * 3 * 2.0}
    assert bwd == {"flops": cells * N * 20.0, "bytes": cells * 5 * 2.0}
    peak, bandwidth = 197e12, 819e9
    for call in (fwd, bwd):
        # The bytes' time is the larger of the two least times.
        assert flops_phi4flash.least_seconds(call, peak, bandwidth) \
            == call["bytes"] / bandwidth > call["flops"] / peak
    assert flops_phi4flash.conv_silu_call("conv_silu_fwd", held, 1, S) == {
        "flops": cells * 12.0, "bytes": cells * 4.0}
    assert flops_phi4flash.conv_silu_call("conv_silu_bwd", held, 1, S) == {
        "flops": cells * 32.0, "bytes": cells * 6.0}
    with pytest.raises(ValueError):
        flops_phi4flash.selective_scan_call("selective_scan", held, 1, S)


def test_step_kernel_calls_count_what_the_step_runs():
    cut = rung(3, 3)
    calls = flops_phi4flash.step_kernel_calls(
        cut, 1, S, 512, 512, True, {"window": False, "causal": True})
    assert {name: one["calls"] for name, one in calls.items()} == {
        "selective_scan_fwd": 8, "selective_scan_bwd": 4,
        "conv_silu_fwd": 8, "conv_silu_bwd": 4,
        "flash_fwd_win": 6, "flash_bwd_dq_win": 3, "flash_bwd_dkv_win": 3,
        "flash_fwd": 4, "flash_bwd_dq": 4, "flash_bwd_dkv": 4}
    # 63 executed tiles a head under the window, 528 causal; q k^T at 64
    # and p V_g at 128 a tile.
    tile = 2.0 * 512 * 512
    assert calls["flash_fwd_win"]["flops"] == H * 63 * tile * (HD + 2 * HD)
    assert calls["flash_fwd"]["flops"] == H * 528 * tile * (HD + 2 * HD)
    assert calls["flash_bwd_dkv"]["flops"] == H * 528 * tile * (
        2 * HD + 2 * 2 * HD)
    assert calls["flash_fwd"]["bytes"] == H * S * 2 * (2 * HD + 2 * 2 * HD)
    # Kept everywhere, or nowhere rematerialised: once a layer.
    kept = flops_phi4flash.step_kernel_calls(cut, 1, S, 512, 512, True)
    assert kept["flash_fwd_win"]["calls"] == 3
    plain = flops_phi4flash.step_kernel_calls(cut, 1, S, 512, 512, False)
    assert plain["selective_scan_fwd"]["calls"] == 4 \
        and plain["flash_fwd_win"]["calls"] == 3
    # A window the sequence does not reach: causal kernels for all.
    short = flops_phi4flash.step_kernel_calls(cut, 1, 512, 512, 512, True)
    assert "flash_fwd_win" not in short and short["flash_fwd"]["calls"] == 7
