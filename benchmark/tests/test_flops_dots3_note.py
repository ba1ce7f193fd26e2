"""``flops_dots3_note.py`` against counts made by hand for the configuration
in the benchmark (dots3-note-prev, one chip of the 32 that share a layer:
published layers 0-4, 8 of 256 experts, 19008 of the vocabulary, one
sequence of 8192), the 1.822 B it holds and the row's 288B-A17B."""

import os

import pytest

import flops_dots3_note as counts
import harness

D, F, FE, V, TOPK, WINDOW = 5120, 13824, 1536, 19008, 2048, 513
# Full: heads, q rank, kv rank, nope, rope, v. Window: the same under swa_.
H, QR, RANK, NOPE, ROPE, VD = 128, 1024, 512, 128, 64, 128
WH, WQR, WRANK, WNOPE, WROPE, WVD = 64, 1024, 1024, 192, 64, 128
J, E = 64, 128


def config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "dots3_note":
            return held
    raise AssertionError("no dots3_note configuration")


S = config()["layout"]["seq_len"]


def test_layers_and_parameters():
    held = config()
    assert S in (8192, 4096)
    assert counts.layers_run(held) == [0, 1, 2, 3, 4]
    assert counts.layer_kinds(held) == [
        (True, "full"), (False, "full"), (False, "window"),
        (False, "window"), (False, "window")]
    assert counts.layer_counts(held) == {
        "layers": 5, "dense": 1, "moe": 4, "full": 2, "window": 3}
    # The two full layers are two runs (dense_full, moe_full) of one layer.
    assert counts.longest_run(held, "full") == 1
    assert counts.longest_run(held, "window") == 3
    full = (D * QR + QR * H * (NOPE + ROPE) + D * (RANK + ROPE)
            + RANK * H * (NOPE + VD) + H * VD * D + D * H)
    assert full == 134_676_480 == counts.attention_params(held, "full")
    window = (D * WQR + WQR * WH * (WNOPE + WROPE) + D * (WRANK + WROPE)
              + WRANK * WH * (WNOPE + WVD) + WH * WVD * D + D * WH)
    assert window == 90_832_896 == counts.attention_params(held, "window")
    indexer = QR * J * E + D * E + D * J
    assert indexer == 9_371_648 == counts.indexer_params(held)
    expert = 3 * D * FE
    assert expert == 23_592_960 == counts.expert_params(held)
    assert counts.held_share(held) == 8 / 256
    # An expert layer on this chip: the router at its whole width, the
    # shared expert, and 8 x 8 / 256 = a quarter of a routed expert a token.
    ffn = D * 256 + expert * (1 + 0.25)
    attention = 2 * (full + indexer) + 3 * window
    assert counts.active_matmul_params(held) == \
        attention + 3 * D * F + 4 * ffn + D * V
    held_ffn = D * 256 + expert * (1 + 8)
    want = attention + 3 * D * F + 4 * held_ffn + 2 * D * V
    assert counts.held_params(held) == want == 1_822_162_944
    # The whole published language model by the same count: 46 layers (1
    # dense; 13 full, each with an indexer, 33 window), 256 experts, the
    # whole tables. The row says 288B-A17B with the towers and the
    # prediction module, which its config does not carry: 279.6 B and
    # 16.3 B a token here, 2.9 % and 4.4 % under.
    whole = counts.published_params(held)
    assert whole == 2 * D * 152064 + 13 * (full + indexer) + 33 * window \
        + 3 * D * F + 45 * (D * 256 + expert * 257)
    assert 279e9 < whole < 280e9 and abs(whole / 288e9 - 1) < 0.03
    active = counts.published_active_params(held)
    assert active == 2 * D * 152064 + 13 * (full + indexer) + 33 * window \
        + 3 * D * F + 45 * (D * 256 + expert * 9)
    assert 16.2e9 < active < 16.3e9 and abs(active / 17e9 - 1) < 0.05


def test_the_pairs_are_the_closed_forms():
    assert counts.selected_pairs(8192, TOPK) == 14_681_088
    assert counts.causal_pairs(8192) == 33_558_528
    assert abs(counts.selected_share(8192, TOPK) - 0.43748) < 1e-5
    assert counts.window_pairs(8192, WINDOW) == 4_071_168 == sum(
        min(t + 1, WINDOW) for t in range(8192))
    assert counts.window_pairs(100, WINDOW) == counts.causal_pairs(100)


def test_model_flops_per_token():
    held = config()
    parts = counts.flops_by_part(held, 8192)
    assert parts["attention_over_selection"] == \
        6 * 2 * H * (192 + VD) * 14_681_088 / 8192
    assert parts["attention_in_window"] == \
        6 * 3 * WH * (256 + WVD) * 4_071_168 / 8192
    assert parts["indexer_scores"] == 6 * 2 * J * E * 33_558_528 / 8192
    assert parts["attention_projections"] == 6 * (
        2 * counts.attention_params(held, "full") + 3 * 90_832_896)
    assert parts["head"] == 6 * D * V
    total = counts.model_flops_per_token(held, 8192)
    assert total == sum(parts.values())
    assert 7.46e9 < total < 7.47e9
    # The attention block is two thirds of a token's cost: its projections
    # 44 %, the products over the selection 12 %, in the windows 3 %, the
    # indexers 7 %.
    assert 0.43 < parts["attention_projections"] / total < 0.44
    assert 0.11 < parts["attention_over_selection"] / total < 0.12
    assert 0.029 < parts["attention_in_window"] / total < 0.030
    assert 0.06 < (parts["indexer_scores"]
                   + parts["indexer_projections"]) / total < 0.07
    attention = sum(value for name, value in parts.items()
                    if name.startswith(("attention", "indexer")))
    assert 0.65 < attention / total < 0.66
    # At 4096 the selection keeps 75 % of fewer causal pairs.
    assert 7.12e9 < counts.model_flops_per_token(held, 4096) < 7.14e9


def test_step_kernel_calls():
    held = config()
    calls = counts.step_kernel_calls(held, 1, 8192, True)
    # 8192 keys = 64 x 128: a full layer's forward outputs are kept and
    # ``dsa_fwd`` runs once a layer; a window layer's 513 keys are not, and
    # ``flash_fwd_win`` runs twice.
    assert {k: v["calls"] for k, v in calls.items()} == {
        "dsa_fwd": 2, "dsa_bwd_dq": 2, "dsa_bwd_dkv": 2, "dsa_probs": 4,
        "flash_fwd_win": 6, "flash_bwd_dq_win": 3, "flash_bwd_dkv_win": 3,
        "gmm": 36, "tgmm": 12}
    assert counts.keeps_forward(8192, VD) and not counts.keeps_forward(
        4096 - 1, VD) and not counts.keeps_forward(WINDOW, WVD)
    shorter = counts.step_kernel_calls(held, 1, 2048, True)
    assert shorter["dsa_fwd"]["calls"] == 4
    once = counts.step_kernel_calls(held, 1, 8192, False)
    assert once["flash_fwd_win"]["calls"] == 3 \
        and once["dsa_probs"]["calls"] == 2 and once["gmm"]["calls"] == 24
    with pytest.raises(ValueError):
        counts.step_kernel_calls(held, 1, 512, True)
    pairs, kept, rows = 14_681_088, 4_071_168, 8192 * 2
    assert calls["dsa_fwd"]["flops"] == H * pairs * 2 * (192 + VD)
    assert calls["dsa_bwd_dq"]["flops"] == H * pairs * 2 * (2 * 192 + VD)
    assert calls["dsa_bwd_dkv"]["flops"] == H * pairs * 2 * (2 * 192 + 2 * VD)
    assert calls["dsa_probs"]["flops"] == H * pairs * 2 * 192
    assert calls["flash_fwd_win"]["flops"] == WH * kept * 2 * (256 + WVD)
    assert calls["flash_bwd_dkv_win"]["flops"] == \
        WH * kept * 2 * (2 * 256 + 2 * WVD)
    square = 8192 * 8192
    assert calls["dsa_fwd"]["bytes"] == H * rows * (2 * 192 + 2 * VD) + square
    assert calls["dsa_probs"]["bytes"] == H * rows * 2 * 192 + 5 * square
    assert calls["flash_bwd_dq_win"]["bytes"] == \
        WH * rows * (3 * 256 + 2 * WVD)
    # The selection's kernels are bound by their products on a v5e, the
    # window's (an eighth of a full layer's pairs at half its heads) by
    # their bytes but for the last.
    for name in ("dsa_fwd", "dsa_bwd_dq", "dsa_bwd_dkv", "dsa_probs"):
        assert calls[name]["flops"] / 197e12 > calls[name]["bytes"] / 819e9
    assert calls["flash_fwd_win"]["flops"] / 197e12 \
        > calls["flash_fwd_win"]["bytes"] / 819e9
    # 2,048 rows under even routing; twice that where the counters say so.
    assert calls["gmm"]["flops"] == 2 * (8192 * 8 / 32) * D * FE
    assert calls["gmm"]["bytes"] == (8192 * 8 / 32) * (D + FE) * 2 \
        + 8 * D * FE * 2
    double = counts.step_kernel_calls(held, 1, 8192, True, 1 / 16)
    assert double["tgmm"]["flops"] == 2 * calls["tgmm"]["flops"]
    assert calls["gmm"]["flops"] / 197e12 < calls["gmm"]["bytes"] / 819e9
