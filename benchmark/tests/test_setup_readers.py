"""The eight readers of set-up, each on a registry made by hand (the
program's two histograms, fed as its span sites and listeners feed them),
and None on an empty one (the parent of the PR that added them)."""

import pytest

import harness
import program_setup
from ray_tpu.util import metrics

NAMES = ("setup.trainer_start_s", "setup.state_init_s", "step.trace_s",
         "step.lower_s", "step.backend_s", "step.first_dispatch_s",
         "setup.other_programs_s", "setup.outside_program_s")
RECORD = {"setup": {"setup_s": 40.0}}
#: (stage, within): seconds. Every outermost stage once; one library built
#: inside ``init`` and one outside it; the runner's lowering after the window.
STAGES = {("init", "none"): 0.5, ("native_build", "init"): 0.3,
          ("native_build", "none"): 1.0, ("worker_group", "none"): 0.02,
          ("backend", "none"): 0.03, ("loop_start", "none"): 0.05,
          ("mesh", "none"): 0.1, ("state_init", "none"): 4.0,
          ("first_call", "none"): 9.0, ("aot_lower", "none"): 2.0}
#: (phase, within): observations, each less what it enclosed.
PHASES = {("trace", "state_init"): [0.2], ("backend", "state_init"): [3.0],
          ("trace", "first_call"): [1.5, 0.25, 0.05], ("lower", "first_call"): [1.0],
          ("backend", "first_call"): [5.0], ("cache_load", "first_call"): [4.5],
          ("trace", "none"): [0.4, 0.1], ("lower", "none"): [0.3],
          ("backend", "none"): [2.0, 0.2], ("cache_load", "none"): [1.9],
          ("trace", "aot_lower"): [0.01], ("lower", "aot_lower"): [1.9]}


@pytest.fixture
def registry():
    metrics.clear_registry()
    yield
    metrics.clear_registry()


def feed():
    from ray_tpu._private import builtin_metrics
    for (stage, within), seconds in STAGES.items():
        builtin_metrics.train_setup_seconds().observe(
            seconds, tags={"stage": stage, "within": within})
    for (phase, within), observed in PHASES.items():
        for seconds in observed:
            builtin_metrics.jax_compile_seconds().observe(
                seconds, tags={"phase": phase, "within": within})


def read(name):
    return harness.load_module("layer_metrics", name).read(RECORD)


@pytest.mark.parametrize("name", NAMES)
def test_none_where_the_program_has_no_such_histogram(registry, name):
    assert read(name) is None


@pytest.mark.parametrize("name,want", [
    ("setup.trainer_start_s", 0.02 + 0.03 + 0.05),
    ("setup.state_init_s", 4.0),
    ("step.trace_s", 1.8),
    ("step.lower_s", 1.0),
    ("step.backend_s", 5.0),  # the cache load lies inside it
    ("step.first_dispatch_s", 9.0 - 1.8 - 1.0 - 5.0),
    ("setup.other_programs_s", 0.5 + 0.3 + 2.2),
    # Less the outermost stages (a library built inside ``init`` is in
    # ``init``; the lowering after the window is not set-up) and the
    # programs made outside any.
    ("setup.outside_program_s",
     40.0 - (0.5 + 1.0 + 0.02 + 0.03 + 0.05 + 0.1 + 4.0 + 9.0) - 3.0),
])
def test_each_reader_on_a_hand_made_registry(registry, name, want):
    feed()
    assert read(name) == pytest.approx(want)


def test_parts_and_remainder_are_the_whole(registry):
    feed()
    parts = program_setup.outermost_stages_seconds() \
        + read("setup.other_programs_s") + read("setup.outside_program_s")
    assert parts == pytest.approx(RECORD["setup"]["setup_s"], abs=1e-9)
    inside = sum(read(name) for name in (
        "step.trace_s", "step.lower_s", "step.backend_s",
        "step.first_dispatch_s"))
    assert inside == pytest.approx(STAGES[("first_call", "none")])


def test_loop_start_is_the_mean_over_this_process_s_ranks(registry):
    feed()
    from ray_tpu._private import builtin_metrics
    builtin_metrics.train_setup_seconds().observe(
        0.15, tags={"stage": "loop_start", "within": "none"})
    assert read("setup.trainer_start_s") == pytest.approx(
        0.02 + 0.03 + (0.05 + 0.15) / 2)


def test_a_first_call_that_made_no_program_reads_zero_not_none(registry):
    from ray_tpu._private import builtin_metrics
    builtin_metrics.train_setup_seconds().observe(
        0.2, tags={"stage": "first_call", "within": "none"})
    assert read("step.lower_s") == 0.0
    assert read("step.first_dispatch_s") == pytest.approx(0.2)
    assert read("setup.other_programs_s") == 0.0


def test_the_entries_move_setup_s_and_list_no_cells():
    spec = harness.load_spec()
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in NAMES:
        assert entries[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_counter", "layer": entries[name]["layer"],
            "moves": "setup_s"}
    assert [entries[n]["layer"] for n in NAMES] == [
        "Train", "step", "step", "step", "step", "step", "step",
        "core runtime"]
