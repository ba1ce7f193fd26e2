"""``flops_glm_moe_dsa.py`` against counts made by hand for the configuration
in the benchmark (GLM-5.2, one chip of the 32 that share a layer: published
layers 2-6, 8 of 256 experts, 19360 of the vocabulary, one sequence of
4096), the 2.674 B it holds and the published model's ~750B."""

import os

import flops_glm_moe_dsa as flops_glm
import harness

D, QR, H, RANK, NOPE, ROPE, VD = 6144, 2048, 64, 512, 192, 64, 256
J, E, F, FE, V, S, TOPK = 32, 128, 12288, 2048, 19360, 4096, 2048


def config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "glm_moe_dsa":
            return held
    raise AssertionError("no glm_moe_dsa configuration")


def test_layers_and_parameters():
    held = config()
    assert held["layout"]["seq_len"] == S
    assert flops_glm.layers_run(held) == [2, 3, 4, 5, 6]
    assert flops_glm.layer_kinds(held) == [
        (True, True), (False, False), (False, False), (False, False),
        (False, True)]
    assert flops_glm.layer_counts(held) == {
        "layers": 5, "dense": 1, "moe": 4, "full": 2}
    assert flops_glm.longest_run(held) == 3
    attention = (D * QR + QR * H * (NOPE + ROPE) + D * (RANK + ROPE)
                 + RANK * H * (NOPE + VD) + H * VD * D)
    assert attention == 165_019_648 == flops_glm.attention_params(held)
    indexer = QR * J * E + D * E + D * J
    assert indexer == 9_371_648 == flops_glm.indexer_params(held)
    expert = 3 * D * FE
    assert expert == 37_748_736 == flops_glm.expert_params(held)
    assert flops_glm.held_share(held) == 8 / 256
    assert flops_glm.router_width(held) == 256
    # An expert layer on this chip: the router at its whole width, the
    # shared expert, and 8 x 8 / 256 = a quarter of a routed expert a token.
    ffn = D * 256 + expert * (1 + 0.25)
    active = 5 * attention + 2 * indexer + 3 * D * F + 4 * ffn + D * V
    assert flops_glm.active_matmul_params(held) == active
    held_ffn = D * 256 + expert * (1 + 8)
    want = 5 * attention + 2 * indexer + 3 * D * F + 4 * held_ffn + 2 * D * V
    assert flops_glm.held_params(held) == want == 2_673_475_584
    # 2.674 B held: 5.35 GB in bfloat16.
    assert abs(want / 1e9 - 2.674) < 0.001
    # The whole published model by the same count: 78 layers (3 dense, 21
    # indexers), 256 experts, the whole tables, one prediction module.
    whole = flops_glm.published_params(held)
    assert 745e9 < whole < 760e9
    tables = 2 * D * 154880
    layers = 78 * attention + 21 * indexer + 3 * 3 * D * F \
        + 75 * (D * 256 + expert * 257)
    module = 2 * D * D + attention + D * 256 + expert * 257
    assert whole == tables + layers + module


def test_the_selected_pairs_are_the_closed_form():
    assert flops_glm.selected_pairs(8192, TOPK) == 14_681_088
    assert flops_glm.causal_pairs(8192) == 33_558_528
    assert flops_glm.selected_pairs(S, TOPK) == 6_292_480
    assert flops_glm.causal_pairs(S) == 8_390_656
    assert abs(flops_glm.selected_share(8192, TOPK) - 0.43748) < 1e-5
    assert abs(flops_glm.selected_share(S, TOPK) - 0.74994) < 1e-5
    assert flops_glm.selected_share(TOPK, TOPK) == 1.0
    assert flops_glm.selected_pairs(100, TOPK) == flops_glm.causal_pairs(100)
    assert flops_glm.selected_pairs(S, TOPK) == sum(
        min(t + 1, TOPK) for t in range(S))


def test_model_flops_per_token():
    held = config()
    parts = flops_glm.flops_by_part(held, S)
    assert parts["attention_over_selection"] == \
        6 * 5 * H * (256 + VD) * 6_292_480 / S
    assert parts["indexer_scores"] == 6 * 2 * J * E * 8_390_656 / S
    assert parts["attention_projections"] == 6 * 5 * 165_019_648
    assert parts["head"] == 6 * D * V
    total = flops_glm.model_flops_per_token(held, S)
    assert total == sum(parts.values())
    assert 9.9e9 < total < 9.93e9
    # The attention's projections are half of it, its products over the
    # selection 15 %, the indexers 2 %.
    assert 0.49 < parts["attention_projections"] / total < 0.51
    assert 0.15 < parts["attention_over_selection"] / total < 0.16
    assert 0.02 < (parts["indexer_scores"]
                   + parts["indexer_projections"]) / total < 0.025
    # At 8192 the selection keeps 44 % of the causal pairs: the attention
    # a token grows by a sixth, not by two.
    longer = flops_glm.flops_by_part(held, 8192)
    assert 1.16 < longer["attention_over_selection"] \
        / parts["attention_over_selection"] < 1.17


def test_step_kernel_calls():
    held = config()
    calls = flops_glm.step_kernel_calls(held, 1, S, True)
    assert {k: v["calls"] for k, v in calls.items()} == {
        "dsa_fwd": 10, "dsa_bwd_dq": 5, "dsa_bwd_dkv": 5, "dsa_probs": 4,
        "gmm": 36, "tgmm": 12}
    once = flops_glm.step_kernel_calls(held, 1, S, False)
    assert once["dsa_fwd"]["calls"] == 5 and once["dsa_probs"]["calls"] == 2 \
        and once["gmm"]["calls"] == 24
    # From S = 32 x 256 on the forward kernel's outputs are kept.
    assert not flops_glm.keeps_forward(held, S)
    assert flops_glm.keeps_forward(held, 8192)
    assert flops_glm.step_kernel_calls(held, 1, 8192, True)["dsa_fwd"][
        "calls"] == 5
    pairs = 6_292_480
    assert calls["dsa_fwd"]["flops"] == H * pairs * 2 * (256 + VD)
    assert calls["dsa_bwd_dq"]["flops"] == H * pairs * 2 * (2 * 256 + VD)
    assert calls["dsa_bwd_dkv"]["flops"] == H * pairs * 2 * (2 * 256 + 2 * VD)
    assert calls["dsa_probs"]["flops"] == H * pairs * 2 * 256
    rows = H * S * 2
    assert calls["dsa_fwd"]["bytes"] == rows * (2 * 256 + 2 * VD) + S * S
    assert calls["dsa_bwd_dkv"]["bytes"] == rows * (3 * 256 + 3 * VD) + S * S
    assert calls["dsa_probs"]["bytes"] == rows * 2 * 256 + 5 * S * S
    # Every one of them is bound by its products on a v5e.
    for name in ("dsa_fwd", "dsa_bwd_dq", "dsa_bwd_dkv", "dsa_probs"):
        assert calls[name]["flops"] / 197e12 > calls[name]["bytes"] / 819e9
    # 1,024 rows under even routing; twice that where the counters say so.
    assert calls["gmm"]["flops"] == 2 * (S * 8 / 32) * D * FE
    assert calls["gmm"]["bytes"] == (S * 8 / 32) * (D + FE) * 2 \
        + 8 * D * FE * 2
    double = flops_glm.step_kernel_calls(held, 1, S, True, 1 / 16)
    assert double["tgmm"]["flops"] == 2 * calls["tgmm"]["flops"]
    # The held experts' weights are most of a grouped product's bytes: it is
    # bound by them.
    assert calls["gmm"]["flops"] / 197e12 < calls["gmm"]["bytes"] / 819e9
