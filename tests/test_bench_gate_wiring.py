"""The regression gate as a HARD gate (tier-1 enforced).

Two layers:

* Wiring — a bench round is produced with ``regression_baseline`` set
  against a USABLE prior round: ``_prior_round_bench`` must skip records
  that carry no comparable numbers (BENCH_r05's and r06's ``parsed`` is
  null — their values survive only in a truncated log tail), and
  ``_regression_gate`` must stamp the baseline name into the extras it is
  given. Both are checked on records the test writes itself, so they hold
  whichever rounds the repo still keeps.

* Enforcement — the latest recorded ``BENCH_r*.json`` may not carry a
  non-empty ``regressions`` list unless every regressed metric is
  waived: either by a ``regressions_waived`` note inside the bench
  record itself or by a matching entry in the repo-level
  ``BENCH_WAIVERS.json``. An unwaived regression fails tier-1 here, so
  a hot-path slowdown can never ride along silently again.
"""

import glob
import importlib.util
import json
import os
import re
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH_PATH = os.path.join(_ROOT, "bench.py")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "_bench_gate_wiring", _BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_bench_gate_wiring"] = mod
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.modules.pop("_bench_gate_wiring", None)


def _bench_rounds():
    rounds = []
    for path in glob.glob(os.path.join(_ROOT, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m:
            rounds.append((int(m.group(1)), path))
    return sorted(rounds)


def _round_regressions(path):
    """Regressed metric names recorded in one bench round — from the
    parsed extras when usable, else recovered from the raw record text
    (r05's parsed payload is null; its regressions list survives only
    inside the truncated ``tail`` string)."""
    with open(path) as f:
        raw = f.read()
    try:
        rec = json.loads(raw)
    except ValueError:
        rec = {}
    parsed = rec.get("parsed") if isinstance(rec, dict) else None
    if isinstance(parsed, dict):
        regs = (parsed.get("extra") or {}).get("regressions")
        if isinstance(regs, list):
            return {r.get("metric") for r in regs if isinstance(r, dict)}
    # Quotes may be escaped (the list often survives only inside the
    # record's quoted ``tail`` string).
    if not re.search(r'\\?"regressions\\?"\s*:', raw):
        return set()
    return set(re.findall(r'\\?"metric\\?"\s*:\s*\\?"([^"\\]+)', raw))


def _waived_metrics(path, rec_round):
    """Union of waivers covering ``rec_round``: the record's own
    ``regressions_waived`` note plus repo-level BENCH_WAIVERS.json."""
    waived = set()
    with open(path) as f:
        raw = f.read()
    try:
        rec = json.loads(raw)
    except ValueError:
        rec = {}
    parsed = rec.get("parsed") if isinstance(rec, dict) else None
    if isinstance(parsed, dict):
        note = (parsed.get("extra") or {}).get("regressions_waived")
        if isinstance(note, (list, tuple)):
            waived.update(note)
    wpath = os.path.join(_ROOT, "BENCH_WAIVERS.json")
    if os.path.exists(wpath):
        with open(wpath) as f:
            doc = json.load(f)
        for w in doc.get("waivers", []):
            if w.get("round") == rec_round:
                waived.update(w.get("metrics", []))
    return waived


@pytest.fixture
def recorded_rounds(bench, tmp_path, monkeypatch):
    """bench.py looks for BENCH_r*.json beside itself: move "itself" to a
    directory holding a usable round 7 under a truncated round 8."""
    usable = {"metric": "m", "value": 100.0,
              "extra": {"tasks_per_sec": 1000.0}}
    (tmp_path / "BENCH_r07.json").write_text(
        json.dumps({"n": 7, "rc": 0, "parsed": usable}))
    (tmp_path / "BENCH_r08.json").write_text(
        json.dumps({"n": 8, "rc": 0, "tail": "...", "parsed": None}))
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    return usable


def test_prior_round_baseline_is_usable(bench, recorded_rounds):
    """The gate baselines against the newest round with numbers to
    compare, not a newer truncated record."""
    prev, name = bench._prior_round_bench()
    assert name == "BENCH_r07.json"
    assert prev == recorded_rounds


def test_unusable_rounds_are_skipped_as_baseline(bench):
    """A round whose parsed payload is null (driver stored only the
    truncated tail) must not become the comparison baseline."""
    rounds = _bench_rounds()
    if not rounds:
        pytest.skip("no BENCH_r*.json recorded yet")
    _, name = bench._prior_round_bench()
    for _, path in rounds:
        with open(path) as f:
            rec = json.load(f)
        parsed = rec.get("parsed") or rec
        usable = isinstance(parsed, dict) and (
            isinstance(parsed.get("extra"), dict)
            or isinstance(parsed.get("value"), (int, float)))
        if os.path.basename(path) == name:
            assert usable, f"gate selected unusable baseline {name}"
        elif not usable:
            assert name != os.path.basename(path)


def test_regression_gate_stamps_baseline(bench, recorded_rounds):
    """bench.py main() calls _regression_gate(extra, headline): the
    produced record carries regression_baseline whenever a prior usable
    round exists, and the drops against it."""
    extra = {"tasks_per_sec": 500.0}
    bench._regression_gate(extra, headline_value=100.0)
    assert extra["regression_baseline"] == "BENCH_r07.json"
    assert [r["metric"] for r in extra["regressions"]] == ["tasks_per_sec"]


def test_main_refuses_to_measure_without_a_tpu(bench):
    """No CPU headline: the measurement path fails where JAX finds no
    accelerator (tier-1 runs on the CPU)."""
    with pytest.raises(SystemExit) as exit_info:
        bench.main([])
    assert "found none" in str(exit_info.value)


def test_unknown_device_kind_is_an_error(bench):
    """Peak FLOP/s and HBM size come from the table or not at all."""
    import types
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert bench._chip_spec(bench.PEAK_FLOPS, v5e) == 197e12
    assert bench._chip_spec(bench.HBM_BYTES, v5e) == 16 << 30
    for table in (bench.PEAK_FLOPS, bench.HBM_BYTES):
        with pytest.raises(ValueError, match="no published figure"):
            bench._chip_spec(table, types.SimpleNamespace(device_kind="cpu"))


def test_check_regressions_flag_wired(bench):
    args = bench._parse_args(["--check-regressions",
                              "--regression-threshold", "15"])
    assert args.check_regressions is True
    assert args.regression_threshold == 15.0


def test_latest_round_regressions_are_waived():
    """HARD GATE: the newest BENCH_r*.json may not record regressions
    that nobody waived. Fix the hot path or add a reasoned waiver."""
    rounds = _bench_rounds()
    if not rounds:
        pytest.skip("no BENCH_r*.json recorded yet")
    _, path = rounds[-1]
    rec_round = os.path.basename(path)
    regressed = _round_regressions(path)
    if not regressed:
        return
    unwaived = regressed - _waived_metrics(path, rec_round)
    assert not unwaived, (
        f"{rec_round} records unwaived regressions {sorted(unwaived)}: "
        "claw the metric back or add a reasoned waiver to "
        "BENCH_WAIVERS.json (round + metrics + reason)")
