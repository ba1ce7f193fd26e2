"""Spans inside Train's save, report and ingest (util/tracing.py): they
record under ``enable_tracing()`` or while ``jax.profiler`` records a
profile, lie in the profile as ``TraceAnnotation``s of the same name, and
cost nothing when neither is on."""

import glob
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import builtin_metrics, trace_assembler
from ray_tpu.air import CheckpointConfig, RunConfig, ScalingConfig, session
from ray_tpu.train import JaxTrainer
from ray_tpu.util import tracing

LOOP = "train-rank-0"
LEAF_ELEMS = 2 * 1024 * 1024  # 8 MB of float32 a leaf: phases, not overhead
HEAD_SHAPE = (1024, 2048)  # 8 MB too, in the transposed memory order


@pytest.fixture
def tracing_off():
    tracing.disable_tracing()
    tracing.set_sample_rate(None)
    tracing.clear_spans()
    yield
    tracing.disable_tracing()
    tracing.set_sample_rate(None)
    tracing.clear_spans()


def _loop(config):
    import jax.numpy as jnp
    # ``head`` arrives as a leaf can come off the chip: whole, but
    # column-major, so it is the one leaf whose bytes the save copies.
    state = {"params": {"w": jnp.ones((LEAF_ELEMS,), jnp.float32),
                        "b": jnp.arange(LEAF_ELEMS, dtype=jnp.float32),
                        "head": np.asfortranarray(
                            np.arange(LEAF_ELEMS, dtype=np.float32).reshape(
                                HEAD_SHAPE))},
             "step": jnp.int32(0)}
    batches = session.get_dataset_shard("train").iter_jax_batches(
        batch_size=4)
    for i in range(config["saves"]):
        next(batches)
        session.report({"i": i})
        session.report_sharded({"i": i}, state, extra={"step": i})


def _fit(tmp_path, saves):
    import ray_tpu.data
    data = ray_tpu.data.from_numpy([np.arange(64).reshape(16, 4)],
                                   column="x")
    return JaxTrainer(
        _loop, train_loop_config={"saves": saves},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="traced", storage_path=str(tmp_path),
            checkpoint_config=CheckpointConfig(num_to_keep=1)),
        datasets={"train": data}).fit()


def test_tracing_imports_without_jax():
    """``ray_tpu.init()`` runs without JAX, so neither ``tracing.py`` nor
    the session's span sites may pull it in, and both the off path and a
    recorded span must work in a process that never imports it."""
    code = (
        "import sys\n"
        "from ray_tpu.util import tracing\n"
        "import ray_tpu.air.session\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "with tracing.start_span('a') as s:\n"
        "    assert s is None\n"
        "tracing.enable_tracing()\n"
        "with tracing.start_span('a') as s:\n"
        "    assert s.thread == 'MainThread' and s.perf_start > 0\n"
        "assert 'jax' not in sys.modules, 'jax imported by a span'\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert done.returncode == 0, done.stderr


def test_off_is_one_shared_no_op(tracing_off):
    first = tracing.start_span("train::report")
    assert first is tracing.start_span("ckpt::commit")
    assert first is tracing.child_span("ckpt::gather")
    with first as span:
        assert span is None
    assert tracing.get_spans() == []


def test_a_profile_turns_recording_on_and_annotates(tracing_off, tmp_path):
    """With ``enable_tracing()`` never called, a span opened while
    ``jax.profiler`` records is in the buffer and is an event of the same
    name in the written ``.xplane.pb``; the sample rate, the operator's
    knob for shipped traces, does not thin a profile."""
    import jax
    import jax.numpy as jnp
    tracing.set_sample_rate(0.0)
    with tracing.start_span("ckpt::before") as span:
        assert span is None
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # host spans only: no Python hooks
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tracing.start_span("ckpt::probe") as span:
            assert span is not None
            with tracing.child_span("ckpt::probe_child"):
                jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    with tracing.start_span("ckpt::after") as span:
        assert span is None
    spans = {s.name: s for s in tracing.get_spans()}
    assert set(spans) == {"ckpt::probe", "ckpt::probe_child"}
    assert spans["ckpt::probe_child"].parent_id == \
        spans["ckpt::probe"].span_id
    assert spans["ckpt::probe"].thread == threading.current_thread().name

    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("ckpt::"):
                        events[ev.name] = (ev.start_ns, ev.duration_ns)
    assert set(events) == {"ckpt::probe", "ckpt::probe_child"}
    # The same nesting on the profile's clock.
    (p0, pd), (c0, cd) = events["ckpt::probe"], events["ckpt::probe_child"]
    assert p0 <= c0 and c0 + cd <= p0 + pd


def test_tracing_off_leaves_nothing(ray_start_regular, tracing_off,
                                    tmp_path):
    result = _fit(tmp_path, saves=1)
    assert result.checkpoint.extra == {"step": 0}
    assert tracing.get_spans() == []


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One tiny job with tracing on: two saves (the second prunes the
    first), then the newest checkpoint read back."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_tpus=0, _memory=1e9)
    tracing.clear_spans()
    tracing.set_sample_rate(None)
    tracing.enable_tracing()
    try:
        result = _fit(tmp_path_factory.mktemp("ckpt"), saves=2)
        result.checkpoint.load_full()
    finally:
        tracing.disable_tracing()
        ray_tpu.shutdown()
    spans = tracing.get_spans()
    tracing.clear_spans()
    return {"spans": spans, "by_id": {s.span_id: s for s in spans},
            "driver": threading.current_thread().name}


# name, parent's name (None: a root), thread ("driver": fit()'s caller).
TABLE = [
    ("train::report_wait", "train::report", LOOP),
    ("train::report_sharded", None, LOOP),
    ("ckpt::meta", "train::report_sharded", LOOP),
    ("ckpt::prefetch", "train::report_sharded", LOOP),
    ("ckpt::gather", "train::report_sharded", LOOP),
    ("ckpt::copy", "train::report_sharded", LOOP),
    ("ckpt::checksum", "train::report_sharded", LOOP),
    ("ckpt::write", "train::report_sharded", LOOP),
    ("ckpt::commit", None, "driver"),
    ("ckpt::prune", "ckpt::commit", "driver"),
    ("ckpt::restore", None, "driver"),
    ("data::next_batch", None, LOOP),
    ("data::to_device", "data::next_batch", LOOP),
]


@pytest.mark.parametrize("name,parent,thread", TABLE)
def test_a_save_yields_the_span(traced_run, name, parent, thread):
    found = [s for s in traced_run["spans"] if s.name == name]
    assert found, sorted({s.name for s in traced_run["spans"]})
    want_thread = traced_run["driver"] if thread == "driver" else thread
    for s in found:
        assert s.duration is not None and s.perf_start > 0
        assert s.thread == want_thread
        up = traced_run["by_id"].get(s.parent_id)
        assert (up.name if up else None) == parent


def test_report_is_a_root_and_a_save_s_ack(traced_run):
    parents = [traced_run["by_id"].get(s.parent_id)
               for s in traced_run["spans"] if s.name == "train::report"]
    names = [p.name if p else None for p in parents]
    # Two plain reports, and one nested in each save as its ack.
    assert names.count(None) == 2
    assert names.count("train::report_sharded") == 2


def _children(traced_run, save):
    return sorted((s for s in traced_run["spans"]
                   if s.parent_id == save.span_id),
                  key=lambda s: s.perf_start)


def test_the_phases_cover_the_save(traced_run):
    saves = [s for s in traced_run["spans"]
             if s.name == "train::report_sharded"]
    assert [s.attributes["seq"] for s in saves] == [1, 2]
    for save in saves:
        children = _children(traced_run, save)
        covered = sum(s.duration for s in children)
        assert 0.95 * save.duration <= covered <= save.duration
        # One after the other on the loop's thread: the save's self time
        # is a true remainder only if no two phases overlap.
        for a, b in zip(children, children[1:]):
            assert a.perf_start + a.duration <= b.perf_start
        # Per leaf: the wait for its transfer, one checksum, one write;
        # a copy for the one leaf that is not C-contiguous; one prefetch;
        # a last write for fsync and rename.
        per_name = {n: sum(s.name == n for s in children)
                    for n in ("ckpt::prefetch", "ckpt::gather", "ckpt::copy",
                              "ckpt::checksum", "ckpt::write")}
        assert per_name == {"ckpt::prefetch": 1, "ckpt::gather": 4,
                            "ckpt::copy": 1, "ckpt::checksum": 4,
                            "ckpt::write": 5}
        nbytes = 3 * LEAF_ELEMS * 4 + 4
        for name in ("ckpt::gather", "ckpt::checksum"):
            assert sum(s.attributes["bytes"] for s in children
                       if s.name == name) == nbytes  # each byte once
        writes = [s for s in children if s.name == "ckpt::write"]
        assert [s.attributes.get("what") for s in writes] == \
            [None] * 4 + ["commit"]
        assert sum(s.attributes["bytes"] for s in writes[:-1]) == nbytes
        assert writes[-1].attributes["bytes"] == nbytes
        assert [s.attributes["leaf"] for s in writes[:-1]] == [
            "params/b", "params/head", "params/w", "step"]  # sorted paths


def test_prefetch_comes_first_and_counts_the_device_leaves(traced_run):
    for save in (s for s in traced_run["spans"]
                 if s.name == "train::report_sharded"):
        names = [s.name for s in _children(traced_run, save)]
        assert names[:3] == ["ckpt::meta", "ckpt::prefetch", "ckpt::gather"]
        [prefetch] = [s for s in _children(traced_run, save)
                      if s.name == "ckpt::prefetch"]
        # ``head`` is a numpy leaf: nothing to start for it.
        assert prefetch.attributes == {"leaves": 3}


def test_copy_is_recorded_only_for_the_leaf_that_was_copied(traced_run):
    copies = [s for s in traced_run["spans"] if s.name == "ckpt::copy"]
    assert [s.attributes for s in copies] == [
        {"leaf": "params/head", "what": "relayout",
         "bytes": LEAF_ELEMS * 4}] * 2  # one a save


def test_a_rank_s_strided_slice_is_a_copy_called_slice(tracing_off,
                                                       tmp_path):
    """The other copy a save can make: a rank's block along a non-leading
    dim. Blocks along dim 0, scalars and whole leaves are written from
    their own memory, with no ``ckpt::copy``."""
    from ray_tpu._private import spill
    from ray_tpu.train._internal import sharded_checkpoint as sc
    flat = {"cols": np.arange(48, dtype=np.float32).reshape(4, 12),
            "rows": np.arange(48, dtype=np.float32).reshape(12, 4),
            "whole": np.ones((5,), np.float32), "scalar": np.float32(3)}
    specs = {"cols": [[], ["fsdp"]], "rows": [["fsdp"], []]}
    tracing.enable_tracing()
    with tracing.start_span("train::report_sharded"):
        sc.write_shard(spill.FileSpillBackend(str(tmp_path)), "t", 1, 1,
                       flat, specs, [("fsdp", 2)])
    copies = [s for s in tracing.get_spans() if s.name == "ckpt::copy"]
    assert [s.attributes for s in copies] == [
        {"leaf": "cols", "what": "slice", "bytes": 4 * 6 * 4}]
    # Outside a save the writer records nothing.
    tracing.clear_spans()
    sc.write_shard(spill.FileSpillBackend(str(tmp_path)), "t", 2, 1, flat,
                   specs, [("fsdp", 2)])
    assert tracing.get_spans() == []


def test_commit_carries_the_seq_and_prune_what_it_dropped(traced_run):
    commits = [s for s in traced_run["spans"] if s.name == "ckpt::commit"]
    assert [s.attributes["seq"] for s in commits] == [1, 2]
    [prune] = [s for s in traced_run["spans"] if s.name == "ckpt::prune"]
    assert prune.parent_id == commits[1].span_id
    assert prune.attributes == {"seqs": [1], "files": 2}
    [restore] = [s for s in traced_run["spans"] if s.name == "ckpt::restore"]
    assert restore.attributes["seq"] == 2


def test_in_process_fields_are_not_shipped(traced_run):
    shipped = traced_run["spans"][0].to_dict()
    assert "perf_start" not in shipped and "thread" not in shipped


@pytest.mark.parametrize("name,stage", [
    ("train::report_sharded", "train_save"),
    ("train::report", "train_report"),
    ("train::report_wait", "train_report"),
    ("ckpt::gather", "ckpt"),
    ("ckpt::commit", "ckpt"),
    ("data::next_batch", "train_ingest"),
    ("data::to_device", "train_ingest"),
    ("data::pull", "pull"),
])
def test_the_summary_groups_train_spans_by_stage(name, stage):
    assert trace_assembler.span_stage({"name": name}) == stage


def test_the_restore_histogram_is_gone():
    assert not hasattr(builtin_metrics, "train_ckpt_restore_seconds")


def test_the_lowered_step_names_its_parts():
    """``jax.named_scope`` on the model's parts and ``name=`` on the three
    Pallas kernels: what a person reads in a profile."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.parallel.train_step import (abstract_train_state,
                                             make_train_step)
    cfg = gpt.config("gpt-tiny", attn_impl="flash", loss_chunk=64)
    mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                      devices=jax.devices()[:1])
    state = abstract_train_state(cfg, mesh)
    step = make_train_step(cfg, mesh)
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = step.lower(state, {"tokens": tokens, "targets": tokens}).as_text(
        debug_info=True)
    for scope in ("block/attention", "block/mlp", "head_loss", "optimizer",
                  "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert scope in text, scope
