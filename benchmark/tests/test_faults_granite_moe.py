"""``check_faults_granite_moe.py`` at the family's tiny size, on the CPU in
float32: the runner's own comparison passes the untouched program and
refuses each planted fault. On the chip the same script runs at the
configuration's size under the configuration's limits, and every fault
decides there too (``UNSEEN`` is empty)."""

import os

import jax
import pytest

import check_faults_granite_moe as script
import harness

SEED = 5


@pytest.fixture(scope="module")
def prepared():
    name = next(
        os.path.basename(entry["file"])[:-len(".json")]
        for entry in harness.load_spec()["configs"]
        if harness.load_json(os.path.join(harness.ROOT, entry["file"]))[
            "program"]["family"] == "granitemoehybrid_moe")
    return script.prepared(name, tiny=True)


@pytest.fixture(scope="module")
def planted(prepared):
    config, family, cfg, mesh = prepared
    params = family.init(cfg, SEED, config["program"], mesh)
    kept = {}
    return lambda fault: script.check(config, family, cfg, mesh, params,
                                      SEED, fault, kept)


NAMES = ["untouched", "no_renormalise", "top_9", "halves_swapped",
         "routed_sum", "shared_swiglu", "residual_on_shared_only",
         "held_shifted", "D", "conv_bias", "gate_after_norm",
         "attention_scale", "kv_pairing", "logits_scaling",
         "eight_bit_residual"]


def test_every_term_of_the_issue_is_planted(prepared):
    assert list(script.faults(prepared[2])) == NAMES
    assert not script.UNSEEN


@pytest.mark.parametrize("name", NAMES)
def test_the_comparison_refuses_the_fault_and_nothing_else(planted, name):
    line = planted(name)
    sound = name == "untouched"
    assert line["ok"] == sound, line
    assert ("logit_rms_tol" in line["failed"]) != sound


def test_the_last_case_frees_the_compiled_programs():
    jax.clear_caches()
