"""``flops_minicpm_sala.py`` against hand counts at the published widths: the
parameters a layer of each kind holds, the chip's and the whole model's, the
pairs the selection defines, the FLOPs a token by part, and the calls,
FLOPs and least bytes of the step's seven Mosaic kernels."""

import os

import flops_minicpm_sala as counts
import harness


def _config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "minicpm_sala":
            return held
    raise AssertionError("no minicpm_sala configuration")


CONFIG = _config()
SEQ = CONFIG["layout"]["seq_len"]


def test_parameters_of_a_layer_the_chip_and_the_model():
    d, f, v = 4096, 16384, 73448
    sparse = 3 * d * d + 2 * d * 256 + 3 * d * f
    linear = 5 * d * d + 3 * d * f
    assert counts.layer_matmul_params(CONFIG, "sparse") == sparse
    assert counts.layer_matmul_params(CONFIG, "lightning") == linear
    assert counts.layer_params(CONFIG, "sparse") == sparse + 2 * d + 2 * 128
    assert counts.layer_params(CONFIG, "lightning") \
        == linear + 2 * d + 3 * 128
    assert counts.layers(CONFIG) == ["sparse"] + ["lightning"] * 3
    held = sparse + 3 * linear + 8 * d + 11 * 128 + 2 * v * d + d
    assert counts.held_params(CONFIG) == held
    assert round(held / 1e6) == 1711
    assert round(counts.published_params(CONFIG) / 1e9, 2) == 9.48


def test_pairs_of_the_selection_and_of_the_compressed_scores():
    assert counts.selected_pairs(CONFIG, 16384) == 58_335_232
    assert counts.causal_pairs(16384) == 134_225_920
    assert abs(counts.selected_share(CONFIG, 16384) - 0.43460) < 1e-5
    # At or under dense_len every causal pair, and nothing is scored.
    assert counts.selected_pairs(CONFIG, 8192) == counts.causal_pairs(8192)
    assert counts.visible_kernels(CONFIG, 8192) == 0
    # A query at t sees the kernels that end at or before it: none before
    # 31, one from 31 to 46, ... 1023 at the last position.
    brute = sum(max((t - 31) // 16 + 1, 0) for t in range(16384))
    assert counts.visible_kernels(CONFIG, 16384) == brute
    assert (16383 - 31) // 16 + 1 == 1023


def test_flops_a_token_by_part():
    parts = counts.flops_by_part(CONFIG, SEQ)
    d, f = 4096, 16384
    assert parts["ffn"] == 6.0 * 4 * 3 * d * f
    assert parts["head"] == 6.0 * d * 73448
    assert parts["sparse_projections"] == 6.0 * (3 * d * d + 2 * d * 256)
    assert parts["lightning_projections"] == 6.0 * 3 * 5 * d * d
    assert parts["sparse_attention_over_pairs"] \
        == 6.0 * 32 * 2 * 128 * 58_335_232 / 16384
    assert parts["recurrence"] == 3.0 * 3 * 32 * 2.0 * (
        2 * 256 * 128 + 2 * 128 * 128)
    assert parts["compressed_scores"] \
        == 2.0 * 32 * 128 * counts.visible_kernels(CONFIG, SEQ) / SEQ
    total = counts.model_flops_per_token(CONFIG, SEQ)
    assert abs(total - sum(parts.values())) < 1.0
    assert round(total * SEQ / 1e12, 1) == 142.5
    assert 0.20 < parts["head"] / total < 0.22
    assert 0.55 < parts["ffn"] / total < 0.56


def test_the_steps_kernel_calls():
    calls = counts.step_kernel_calls(CONFIG, 1, SEQ, remat=True)
    assert {name: one["calls"] for name, one in calls.items()} == {
        "sala_fwd": 1, "sala_bwd_dq": 1, "sala_bwd_dkv": 1,
        "lightning_fwd": 6, "lightning_bwd": 3,
        "gated_norm_fwd": 6, "gated_norm_bwd": 3}
    plain = counts.step_kernel_calls(CONFIG, 1, SEQ, remat=False)
    assert plain["lightning_fwd"]["calls"] == 3
    assert plain["gated_norm_fwd"]["calls"] == 3
    pairs = 32 * 58_335_232 * 2.0 * 128
    assert calls["sala_fwd"]["flops"] == 2 * pairs
    assert calls["sala_bwd_dq"]["flops"] == 3 * pairs
    assert calls["sala_bwd_dkv"]["flops"] == 4 * pairs
    # q and out a query head, k and v a KV head, the selection a byte a
    # (query, block) and group.
    assert calls["sala_fwd"]["bytes"] == SEQ * (
        128 * 2 * (2 * 32 + 2 * 2) + 2 * 256)
    chunks = SEQ // 256
    assert calls["lightning_fwd"]["flops"] == 32 * chunks * 2.0 * (
        2 * 256 * 256 * 128 + 2 * 256 * 128 * 128)
    assert calls["lightning_bwd"]["flops"] == 32 * chunks * 2.0 * (
        5 * 256 * 256 * 128 + 4 * 256 * 128 * 128)
    assert calls["lightning_fwd"]["bytes"] == 32 * (
        4 * SEQ * 128 * 2 + chunks * 128 * 128 * 4)
    assert calls["gated_norm_fwd"] == {"flops": 0.0, "calls": 6,
                                       "bytes": 3.0 * SEQ * 4096 * 2}
    assert calls["gated_norm_bwd"]["bytes"] == 5.0 * SEQ * 4096 * 2


def test_a_forward_kept_or_run_twice():
    assert counts.keeps_forward(CONFIG, 16384)
    wide = dict(CONFIG, head_dim=256)
    assert not counts.keeps_forward(wide, 16384)
    assert counts.step_kernel_calls(wide, 1, SEQ, True)["sala_fwd"]["calls"] \
        == 2
