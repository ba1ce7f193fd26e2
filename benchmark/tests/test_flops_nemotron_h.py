"""``flops_nemotron_h.py`` against counts made by hand for the configuration
in the benchmark (NVIDIA-Nemotron-3-Nano-30B-A3B: published layers 34-42 of
52, ``EMEMEMEM*``, 32 of 128 experts of 1856 at 6 a token beside a shared
expert of 3712, a quarter of the vocabulary, two sequences of 16384)."""

import os

import flops_nemotron_h as counts
import harness

D, V, S = 2688, 32768, 16384
DI, CONV, MH, N, G = 4096, 6144, 64, 128, 8
HEADS, KV, HD = 32, 2, 128
F, FS, E, HELD, K = 1856, 3712, 128, 32, 6


def config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "nemotron_h":
            return held
    raise AssertionError("no nemotron_h configuration")


def test_layers_and_parameters():
    held = config()
    assert counts.layer_counts(held) == {"mamba": 4, "experts": 4,
                                         "attention": 1}
    assert counts.layer_counts(held, published=True) == {
        "mamba": 23, "experts": 23, "attention": 6}
    assert "".join(k[0] for k in counts.layer_kinds(held)) == "emememema"
    assert (counts.d_inner(held), counts.conv_dim(held)) == (DI, CONV)
    mamba = D * (DI + CONV + MH) + DI * D
    assert mamba == 38_707_200 == counts.mamba_params(held)  # 27.70 + 11.01
    attention = 2 * D * HD * (HEADS + KV)
    assert attention == 23_396_352 == counts.attention_params(held)
    expert = 2 * D * F
    assert expert == 9_977_856 == counts.expert_params(held)
    assert counts.shared_params(held) == 2 * D * FS == 19_955_712
    assert counts.router_width(held) == E and counts.held_share(held) == 0.25
    # The issue's count: an expert layer at 32 held experts 339.6 M, whole
    # 1,297.5 M; 1.713 B held, 31.58 B published, 3.2 B a token.
    layer_held = D * E + E + HELD * expert + 2 * D * FS
    assert round(layer_held / 1e6, 1) == 339.6
    assert round((D * E + E + E * expert + 2 * D * FS) / 1e6, 1) == 1297.5
    assert counts.held_params(held) == 1_712_918_016
    assert counts.published_params(held) == 31_577_940_288
    active = 4 * mamba + attention + 4 * (
        D * E + 2 * D * FS + K * expert * 0.25) + D * V
    assert counts.active_matmul_params(held) == active == 407_371_776
    assert round(counts.active_matmul_params(held, published=True) / 1e9,
                 2) == 3.23
    # The head is 22 % of the matmul parameters a token goes through here,
    # 11 % in the whole model.
    assert round(D * V / active, 2) == 0.22
    assert round(D * 131072 / counts.active_matmul_params(held, True), 2) \
        == 0.11


def test_model_flops_per_token():
    held = config()
    scan = 15 * DI * N
    assert scan == counts.scan_flops_per_token(held) == 7_864_320
    want = 6 * 407_371_776 + 12 * HEADS * HD * S + 4 * scan
    assert counts.model_flops_per_token(held, S) == want == 3_280_994_304
    # Its parts of the 3.28 GFLOP: matmul parameters 2.44 (the head 0.53),
    # the one attention layer's scores and values 0.81, the scans 0.03.
    assert round(6 * 407_371_776 / 1e9, 2) == 2.44
    assert round(6 * D * V / 1e9, 2) == 0.53
    assert round(12 * HEADS * HD * S / 1e9, 2) == 0.81
    assert round(4 * scan / 1e9, 2) == 0.03


def test_step_kernel_calls():
    held = config()
    calls = counts.step_kernel_calls(held, 2, S, 512, 512, True, 128)
    assert counts.keeps_forward(S, HD)
    assert {k: v["calls"] for k, v in calls.items()} == {
        "ssd_fwd": 8, "ssd_bwd": 4, "conv_silu_fwd": 8, "conv_silu_bwd": 4,
        "gated_norm_fwd": 8, "gated_norm_bwd": 4, "flash_fwd": 1,
        "flash_bwd_dq": 1, "flash_bwd_dkv": 1, "gmm": 24, "tgmm": 8,
        "moe_rows_to_tokens": 8}
    once = counts.step_kernel_calls(held, 2, S, 512, 512, False, 128)
    assert once["ssd_fwd"]["calls"] == 4 and once["gmm"]["calls"] == 24
    # The scan: C B^T once a chunk and group, the rest a head.
    chunks, L = 2 * S // 128, 128
    square, with_state = 2 * L * L, 2 * L * N * 64
    assert calls["ssd_fwd"]["flops"] == chunks * (
        G * square * N + MH * (square * 64 + 2 * with_state))
    assert calls["ssd_bwd"]["flops"] == chunks * (
        3 * G * square * N + MH * (2 * square * 64 + 5 * with_state))
    tokens = 2 * S
    assert calls["ssd_fwd"]["bytes"] == 2 * tokens * DI * 2 \
        + 2 * tokens * G * N * 2 + chunks * MH * N * 64 * 4 \
        + 5 * tokens * MH * 4
    wider = counts.step_kernel_calls(held, 2, S, 512, 512, True, 256)
    assert wider["ssd_fwd"]["flops"] > calls["ssd_fwd"]["flops"]
    assert wider["ssd_fwd"]["bytes"] < calls["ssd_fwd"]["bytes"]
    # The row passes move whole arrays and multiply nothing worth counting.
    assert calls["conv_silu_bwd"] == {
        "flops": 0.0, "bytes": 3.0 * tokens * CONV * 2, "calls": 4}
    assert calls["gated_norm_bwd"]["bytes"] == 5.0 * tokens * DI * 2
    # The grouped products at the rows of this chip's share: a quarter of
    # 32768 x 6 under even routing, or what the counters measured.
    rows = tokens * K // 4
    assert rows == 49_152
    assert calls["gmm"]["flops"] == 2 * rows * D * F == 490_431_578_112
    assert calls["gmm"]["bytes"] == rows * (D + F) * 2 + HELD * D * F * 2
    assert calls["tgmm"]["flops"] == calls["gmm"]["flops"]
    assert calls["moe_rows_to_tokens"]["bytes"] == rows * D * 2 \
        + tokens * D * 4
    measured = counts.step_kernel_calls(held, 2, S, 512, 512, True, 128,
                                        share=0.3)
    assert measured["gmm"]["flops"] == 2 * tokens * K * 0.3 * D * F
    # Every query head against its own copy of K and V.
    tile = 2 * 512 * 512 * HD
    assert calls["flash_fwd"]["flops"] == 2 * HEADS * 528 * 2 * tile
    # On a v5e the flash kernels and the grouped products are bound by
    # compute; the row passes, the way back and, at chunks of 128, the scans
    # (whose products over [L, L] grow with the chunk) by bytes.
    for name, call in calls.items():
        by_compute = call["flops"] / 197e12 > call["bytes"] / 819e9
        assert by_compute == name.startswith(("flash", "gmm", "tgmm"))
