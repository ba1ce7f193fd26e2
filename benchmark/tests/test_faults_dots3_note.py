"""``check_faults_dots3_note.py`` at the family's tiny size, on the CPU in
float32: the runner's own comparison passes the untouched program and
refuses each planted fault (a gate or a rescale left out, the window one key
short, the rope bases swapped, the selection ignored, the residual stream
at 8 bits). On the chip the same script runs at the configuration's size
under the configuration's limits."""

import os

import jax
import pytest

import check_faults_dots3_note as script
import harness

NAMES = list(script.faults())
SEED = 5


@pytest.fixture(scope="module", autouse=True)
def leave_no_programs_behind():
    """The suite in one process sits at the kernel's limit on memory maps
    (``vm.max_map_count`` 65,530: every compiled CPU program keeps some),
    and a later file's compile segfaults past it: what this file compiled
    goes when it is done."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def planted():
    name = next(
        os.path.basename(entry["file"])[:-len(".json")]
        for entry in harness.load_spec()["configs"]
        if harness.load_json(os.path.join(harness.ROOT, entry["file"]))[
            "program"]["family"] == "dots3_note")
    config, family, cfg, mesh = script.prepared(name, tiny=True)
    params = family.init(cfg, SEED, config["program"])
    kept = {}
    return lambda fault: script.check(config, family, cfg, mesh, params,
                                      SEED, fault, kept)


def test_every_term_of_the_issue_is_planted():
    assert set(NAMES) == {
        "untouched", "gate_full", "gate_window", "s_q", "s_kv", "window_512",
        "rope_bases_swapped", "selection", "relu", "shared_expert",
        "eight_bit_residual"}


@pytest.mark.parametrize("name", NAMES)
def test_the_comparison_refuses_the_fault_and_nothing_else(planted, name):
    line = planted(name)
    if name in script.FAINT:
        # One key of 129 a query: inside the limits, and still a hundred
        # times what the untouched program reads.
        assert line["logit_rms_err"] > 100 * planted("untouched")[
            "logit_rms_err"]
        return
    assert line["ok"] == (name == "untouched"), line
    assert ("logit_rms_tol" in line["failed"]) == (name != "untouched")


def test_a_faint_fault_says_what_tells_it():
    assert set(script.FAINT) == {"window_512"} < set(NAMES)
    assert "tests/test_dots3_note.py" in script.FAINT["window_512"]
