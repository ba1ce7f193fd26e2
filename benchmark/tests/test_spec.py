"""``BENCHMARK.json`` against the files it names, and the command's refusal
to run without a TPU."""

import os
import subprocess
import sys

import harness

SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_every_named_file_exists_and_names_are_legal():
    spec = harness.load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    inside = tuple(p.rstrip("/") + "/" for p in spec["paths"])
    for word in spec["command"]:
        assert not word.startswith("/") and ".." not in word
    assert os.path.isfile(os.path.join(harness.ROOT, spec["command"][1]))
    configs = {}
    for config in spec["configs"]:
        assert harness.NAME.match(config["name"])
        assert config["file"].startswith(inside)
        held = harness.load_json(os.path.join(harness.ROOT, config["file"]))
        assert sorted(held["reduced"]) == sorted(config["reduced"])
        assert held["source"] == config["source"]
        for key in config["reduced"]:
            assert harness.NAME.match(key)
            assert not key.endswith(("_dim", "_rank")) and key not in (
                "n_embd", "n_inner", "n_head")
        for kind, group in (("families", "program"),
                            ("reference", "reference")):
            assert os.path.isfile(os.path.join(
                harness.HERE, kind, held[group]["family"] + ".py"))
        configs[config["name"]] = held
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)
    used = set()
    for cell in spec["workloads"]:
        assert harness.NAME.match(cell["name"])
        assert harness.NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        assert cell["config"] in configs
        assert configs[cell["config"]]["layout"]["chips"] == cell["chips"]
        used.add(cell["config"])
        traffic = harness.load_cell(spec, cell["name"]).traffic
        assert os.path.isfile(os.path.join(
            harness.HERE, "runners", traffic["runner"] + ".py"))
    assert used == set(configs)
    pairs = [(c["config"], c["traffic"]) for c in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(c["chips"] == 4 for c in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 4)


def test_metrics_have_readers_and_legal_names():
    spec = harness.load_spec()
    cells = {w["name"] for w in spec["workloads"]}
    end_to_end = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in end_to_end
    names = []
    for group, kind in (("end_to_end", "end_to_end"),
                        ("per_layer", "layer_metrics")):
        for metric in spec[group]:
            names.append(metric["name"])
            assert harness.NAME.match(metric["name"])
            assert harness.UNIT.match(metric["unit"])
            assert metric["better"] in ("lower", "higher")
            assert metric["source"] in SOURCES
            assert set(metric.get("workloads", cells)) <= cells
            assert callable(harness.load_module(kind, metric["name"]).read)
            if group == "end_to_end":
                assert 0.01 <= metric["bound"] <= 0.1
                assert metric["source"] in ("host_clock", "device_trace")
                assert set(metric) <= {"name", "unit", "better", "bound",
                                       "source", "workloads"}
            else:
                assert metric["moves"] in end_to_end
                assert set(metric) <= {"name", "unit", "better", "source",
                                       "layer", "moves", "workloads"}
    assert len(set(names)) == len(names)
    for cell in cells:
        own = {m["name"] for m in harness.metrics_of(spec, "end_to_end",
                                                     cell)}
        assert "setup_s" in own and len(own) >= 2
        layers = harness.metrics_of(spec, "per_layer", cell)
        # A per-layer metric is reported only where the metric it moves is.
        assert layers and all(m["moves"] in own for m in layers)


def test_every_moves_names_an_end_to_end_metric_of_the_same_cells():
    """A per-layer metric is reported only where the metric it moves is, so
    a ``moves`` that names no end-to-end metric (one renamed or taken
    away) or one of other cells silences its reader."""
    spec = harness.load_spec()
    cells = [w["name"] for w in spec["workloads"]]
    end_to_end = {m["name"]: set(m.get("workloads", cells))
                  for m in spec["end_to_end"]}
    for metric in spec["per_layer"]:
        assert metric["moves"] in end_to_end, metric["name"]
        assert set(metric.get("workloads", cells)) <= \
            end_to_end[metric["moves"]], metric["name"]
    # Every reader file is some metric's and every metric has its file.
    for group, kind in (("end_to_end", "end_to_end"),
                        ("per_layer", "layer_metrics")):
        files = {f[:-3] for f in os.listdir(os.path.join(harness.HERE, kind))
                 if f.endswith(".py")}
        assert files == {m["name"] for m in spec[group]}


def test_no_file_names_a_cell_a_configuration_or_a_metric_in_code():
    spec = harness.load_spec()
    names = [w["name"] for w in spec["workloads"]] + \
        [c["name"] for c in spec["configs"]]
    for folder, _, files in os.walk(harness.HERE):
        if os.path.basename(folder) in ("tests", "__pycache__"):
            continue
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(folder, name)) as f:
                source = f.read()
            for cell in names:
                assert cell not in source, (name, cell)


def test_run_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = harness.load_spec()["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         cell, "--seed", "0", "--seconds", "1", "--trace", "0"],
        env=env, cwd=harness.ROOT, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr
    assert '"correct"' not in done.stdout
