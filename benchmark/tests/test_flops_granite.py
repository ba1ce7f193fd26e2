"""``flops_granite.py`` against counts made by hand for the configuration in
the benchmark (granite-4.0-h-micro, 20 of 40 layers, one sequence of
32768)."""

import os

import flops_granite
import harness

D, F, V, S = 2048, 8192, 100352, 32768
HEADS, WIDTH, STATE, CHUNK = 64, 64, 128, 256
D_INNER = HEADS * WIDTH


def config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "granitemoehybrid":
            return held
    raise AssertionError("no granitemoehybrid configuration")


def test_layers_and_matmul_parameters():
    held = config()
    assert flops_granite.layer_counts(held) == {"mamba": 18, "attention": 2}
    whole = dict(held, num_hidden_layers=40)
    assert flops_granite.layer_counts(whole) == {"mamba": 36, "attention": 4}
    mamba = D * (D_INNER + D_INNER + 2 * STATE + HEADS) + D_INNER * D
    assert mamba == 25_821_184 == flops_granite.mamba_params(held)
    # 32 query heads and 8 KV heads of 2048 / 32 = 64.
    attention = 2 * D * D + 2 * D * 8 * 64
    assert attention == 10_485_760 == flops_granite.attention_params(held)
    assert flops_granite.mlp_params(held) == 3 * D * F == 50_331_648
    n = 18 * mamba + 2 * attention + 20 * 3 * D * F + D * V
    assert n == 1_697_906_688 == flops_granite.matmul_params(held)
    assert flops_granite.matmul_params(whole) == 3_190_292_480


def test_model_flops_per_token():
    held = config()
    scan = 15 * HEADS * WIDTH * STATE
    assert flops_granite.scan_flops_per_token(held) == scan == 7_864_320
    want = 6 * 1_697_906_688 + 12 * 2 * D * S + 18 * scan
    got = flops_granite.model_flops_per_token(held, S)
    assert got == want == 11_939_610_624
    # The tied head is a tenth of it here, a twentieth in the whole model.
    assert 0.10 < 6 * D * V / got < 0.11
    whole = flops_granite.model_flops_per_token(
        dict(held, num_hidden_layers=40), S)
    assert 0.05 < 6 * D * V / whole < 0.06


def test_ssd_kernels_flops_and_bytes():
    held = config()
    chunks = S // CHUNK
    square, with_state = 2 * CHUNK * CHUNK, 2 * CHUNK * STATE * WIDTH
    fwd = flops_granite.ssd_call("ssd_fwd", held, 1, S)
    assert fwd["flops"] == chunks * (
        square * STATE + HEADS * (square * WIDTH + 2 * with_state))
    assert fwd["flops"] == 139_586_437_120
    wide, states = S * D_INNER * 2, chunks * HEADS * STATE * WIDTH * 4
    vectors, shared = S * HEADS * 4, S * STATE
    assert fwd["bytes"] == 2 * wide + 4 * shared + states + 5 * vectors
    bwd = flops_granite.ssd_call("ssd_bwd", held, 1, S)
    assert bwd["flops"] == chunks * (
        3 * square * STATE + HEADS * (2 * square * WIDTH + 5 * with_state))
    assert bwd["bytes"] == 3 * wide + 12 * shared + states + 10 * vectors
    # Bandwidth bounds the forward on a v5e, compute the backward.
    assert fwd["bytes"] / 819e9 > fwd["flops"] / 197e12
    assert bwd["bytes"] / 819e9 < bwd["flops"] / 197e12


def test_step_kernel_calls():
    held = config()
    calls = flops_granite.step_kernel_calls(held, 1, S, 512, 512, True)
    assert {k: v["calls"] for k, v in calls.items()} == {
        "ssd_fwd": 36, "ssd_bwd": 18, "flash_fwd": 4, "flash_bwd_dq": 2,
        "flash_bwd_dkv": 2}
    once = flops_granite.step_kernel_calls(held, 1, S, 512, 512, False)
    assert once["ssd_fwd"]["calls"] == 18 and once["flash_fwd"]["calls"] == 2
    # 2,080 executed tiles a head of 64 x 65 / 2; 32 query heads of 64.
    tiles = 64 * 65 // 2
    assert calls["flash_fwd"]["flops"] == 32 * tiles * 2 * 512 * 512 * 128
    assert calls["flash_bwd_dkv"]["flops"] == 2 * calls["flash_fwd"]["flops"]
