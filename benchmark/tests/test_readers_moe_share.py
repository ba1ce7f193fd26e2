"""``moe.compact_share`` on a registry made by hand: None where the program
feeds no such counter (the parent of the PR that added the bound), else the
calls within the bound over the calls in all."""

import pytest

import harness
import program_counters


def read(name):
    return harness.load_module("layer_metrics", name).read({})


@pytest.fixture
def counters(monkeypatch):
    """The program's registry as a dictionary the test fills."""
    held = {}
    monkeypatch.setattr(program_counters, "value", held.get)
    return held


def test_compact_share(counters):
    assert read("moe.compact_share") is None  # a parent without the bound
    counters["ray_tpu_train_moe_calls_total"] = 148.0
    assert read("moe.compact_share") is None
    counters["ray_tpu_train_moe_calls_within_bound_total"] = 148.0
    assert read("moe.compact_share") == 1.0
    counters["ray_tpu_train_moe_calls_within_bound_total"] = 111.0
    assert read("moe.compact_share") == 0.75
    # Fed, and nothing ran yet: no share of no calls.
    counters["ray_tpu_train_moe_calls_total"] = 0.0
    assert read("moe.compact_share") is None


def test_it_is_listed_for_the_cells_with_a_share():
    """Every cell whose configuration holds a share of the experts
    (``experts_held``) and no other: by name, wherever the entry stands."""
    spec = harness.load_spec()
    entry, = (m for m in spec["per_layer"] if m["name"] == "moe.compact_share")
    with_a_share = [
        w["name"] for w in spec["workloads"]
        if "experts_held" in harness.load_cell(
            spec, w["name"]).config["program"]["overrides"]]
    assert with_a_share[:2] == ["trinity-large-preview-1chip.steady",
                                "kimi-linear-48b-a3b-1chip.steady"]
    assert entry == {
        "name": "moe.compact_share", "unit": "ratio", "better": "higher",
        "source": "program_counter", "layer": "expert layer",
        "moves": "tokens_per_s", "workloads": with_a_share}
