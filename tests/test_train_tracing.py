"""Spans inside Train's save, report and ingest (util/tracing.py): they
record under ``enable_tracing()`` or while ``jax.profiler`` records a
profile, lie in the profile as ``TraceAnnotation``s of the same name, and
cost nothing when neither is on."""

import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import builtin_metrics, trace_assembler
from ray_tpu.air import CheckpointConfig, RunConfig, ScalingConfig, session
from ray_tpu.train import JaxTrainer
from ray_tpu.util import tracing

LOOP = "train-rank-0"
WRITER = "ckpt-writer-0"  # checksum, write, fsync: beside the loop
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
    # By the prefix, as the profile below: the jitted sum's ``compile::*``
    # and a running sampler's ``host::tick`` record under a profile too.
    spans = {s.name: s for s in tracing.get_spans()
             if s.name.startswith("ckpt::")}
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
    ("ckpt::drain_wait", "train::report_sharded", LOOP),
    ("ckpt::meta", "train::report_sharded", LOOP),
    ("ckpt::prefetch", "train::report_sharded", LOOP),
    ("ckpt::gather", "train::report_sharded", LOOP),
    ("ckpt::copy", "train::report_sharded", WRITER),
    ("ckpt::checksum", "train::report_sharded", WRITER),
    ("ckpt::write", "train::report_sharded", WRITER),
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


def _children(traced_run, save, thread=None):
    return sorted((s for s in traced_run["spans"]
                   if s.parent_id == save.span_id
                   and thread in (None, s.thread)),
                  key=lambda s: s.perf_start)


# The save's direct children by the thread they ran on: how many of each
# name; the loop's lie inside the save's span (the stall), the writer's
# begin where it hands over and end after it.
PHASES = {
    LOOP: {"ckpt::drain_wait": 1, "ckpt::meta": 2, "ckpt::prefetch": 1,
           "ckpt::gather": 4, "train::report": 1},
    WRITER: {"ckpt::copy": 1, "ckpt::checksum": 4, "ckpt::write": 5},
}


@pytest.mark.parametrize("thread", [LOOP, WRITER])
def test_the_phases_cover_the_save(traced_run, thread):
    saves = [s for s in traced_run["spans"]
             if s.name == "train::report_sharded"]
    assert [s.attributes["seq"] for s in saves] == [1, 2]
    for save in saves:
        children = _children(traced_run, save, thread)
        assert {n: sum(s.name == n for s in children)
                for n in PHASES[thread]} == PHASES[thread]
        assert len(children) == sum(PHASES[thread].values())
        # One after the other on their thread: a sum of them is a time.
        for a, b in zip(children, children[1:]):
            assert a.perf_start + a.duration <= b.perf_start
        end = save.perf_start + save.duration
        covered = sum(s.duration for s in children)
        if thread == LOOP:
            # The stall is its phases: what none of them covers is the
            # writer thread's start (these saves are of milliseconds, and
            # a new thread takes the interpreter for a few).
            assert save.duration - 0.1 <= covered <= save.duration
            assert children[-1].perf_start + children[-1].duration <= end
        else:
            # Handed over once the last leaf is on the host, ahead of the
            # report; over after the stall is.
            gathered = [s for s in _children(traced_run, save, LOOP)
                        if s.name == "ckpt::gather"][-1]
            assert gathered.perf_start + gathered.duration \
                <= children[0].perf_start
            assert children[-1].perf_start + children[-1].duration > end


@pytest.mark.parametrize("name", ["ckpt::gather", "ckpt::checksum",
                                  "ckpt::write"])
def test_each_byte_passes_each_phase_once(traced_run, name):
    """Per leaf: the wait for its transfer, one checksum, one write, in
    sorted path order; a last write for fsync and rename."""
    nbytes = 3 * LEAF_ELEMS * 4 + 4
    for save in (s for s in traced_run["spans"]
                 if s.name == "train::report_sharded"):
        phase = [s for s in _children(traced_run, save) if s.name == name]
        if name == "ckpt::write":
            assert [s.attributes.get("what") for s in phase] == \
                [None] * 4 + ["commit"]
            assert phase[-1].attributes["bytes"] == nbytes
            assert phase[-1].attributes["seq"] == save.attributes["seq"]
            phase = phase[:-1]
        assert sum(s.attributes["bytes"] for s in phase) == nbytes
        assert [s.attributes["leaf"] for s in phase] == [
            "params/b", "params/head", "params/w", "step"]


def test_prefetch_comes_first_and_counts_the_device_leaves(traced_run):
    for save in (s for s in traced_run["spans"]
                 if s.name == "train::report_sharded"):
        names = [s.name for s in _children(traced_run, save)]
        assert names[:4] == ["ckpt::drain_wait", "ckpt::meta",
                             "ckpt::prefetch", "ckpt::gather"]
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
                  "flash_fwd", "flash_bwd"):
        assert scope in text, scope
    assert "flash_bwd_d" not in text


# -- set-up: stages, the step's first call, JAX's compile events -----------

SETUP = "ray_tpu_train_setup_seconds"
COMPILE = "ray_tpu_jax_compile_seconds"
TRAIN_STAGES = ("worker_group", "backend", "loop_start")
LOOP_STAGES = ("mesh", "state_init", "first_call")


def _observations(name):
    """{labels: count} of one of the process's histograms."""
    from ray_tpu.util import metrics
    for entry in metrics.snapshot():
        if entry["name"] == name:
            return dict(entry.get("counts", {}))
    return {}


def _observed_since(name, before):
    now = _observations(name)
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


def _setup_loop(config):
    """A user's loop on ``gpt-tiny``: mesh, state, step, one first call,
    twenty warm ones, one with a new batch shape."""
    if config["fail_once"] and not os.path.exists(config["fail_once"]):
        open(config["fail_once"], "w").close()
        raise RuntimeError("the first attempt dies before any set-up")
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshConfig, compile_events
    from ray_tpu.parallel.train_step import (init_train_state,
                                             make_train_step)
    from ray_tpu.train import prepare_mesh
    mesh = prepare_mesh(MeshConfig(dp=1, fsdp=1, tp=1))
    cfg = gpt.config("gpt-tiny")
    state = init_train_state(cfg, mesh)
    make_train_step(cfg, mesh)  # built and never called
    step = make_train_step(cfg, mesh)

    def batch(rows):
        tokens = jnp.zeros((rows, 64), jnp.int32)
        return {"tokens": tokens, "targets": tokens}

    def first_calls():
        return sum(s.name == "step::first_call"
                   for s in tracing.get_spans())

    state, metrics = step(state, batch(2))
    jax.block_until_ready(metrics)
    after_first = first_calls(), _observations(SETUP).get(
        ("first_call", "none"))
    calls = compile_events.listener_calls
    for _ in range(20):
        state, metrics = step(state, batch(2))
    jax.block_until_ready(metrics)
    warm = {"listener_calls": compile_events.listener_calls - calls,
            "spans": first_calls() - after_first[0],
            "observed": _observations(SETUP).get(
                ("first_call", "none")) - after_first[1]}
    state, metrics = step(state, batch(4))
    from jax._src import monitoring
    listeners = monitoring.get_event_time_span_listeners()
    session.report({
        "warm": warm,
        "listeners": sum(f is compile_events._on_time_span
                         for f in listeners)})


def _setup_fit(tmp_path, fail_once):
    from ray_tpu._private import events
    from ray_tpu.air import FailureConfig
    rows, emit = [], events.emit

    def capture(source, message, **kwargs):
        rows.append((source, message, kwargs))
        emit(source, message, **kwargs)

    ray_tpu.shutdown()
    before = {name: _observations(name) for name in (SETUP, COMPILE)}
    events.emit = capture
    try:
        ray_tpu.init(num_cpus=4, num_tpus=1, _memory=1e9)
        result = JaxTrainer(
            _setup_loop,
            train_loop_config={
                "fail_once": str(tmp_path / "died") if fail_once else ""},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         tpus_per_worker=1),
            run_config=RunConfig(failure_config=FailureConfig(
                max_failures=int(fail_once)))).fit()
    finally:
        events.emit = emit
        ray_tpu.shutdown()
    return {"metrics": result.metrics, "journal": rows,
            "setup": _observed_since(SETUP, before[SETUP]),
            "compile": _observed_since(COMPILE, before[COMPILE]),
            "driver": threading.current_thread().name}


@pytest.fixture(scope="module")
def setup_traced(tmp_path_factory):
    """One tiny job with tracing on, no failure."""
    tracing.clear_spans()
    tracing.set_sample_rate(None)
    tracing.enable_tracing()
    try:
        run = _setup_fit(tmp_path_factory.mktemp("setup"), fail_once=False)
    finally:
        tracing.disable_tracing()
    spans = tracing.get_spans()
    tracing.clear_spans()
    return dict(run, spans=spans, by_id={s.span_id: s for s in spans})


@pytest.fixture(scope="module")
def setup_restarted(tmp_path_factory):
    """The same job with tracing off; its first attempt dies at the train
    function's first statement and the gang restarts once."""
    tracing.disable_tracing()
    tracing.clear_spans()
    run = _setup_fit(tmp_path_factory.mktemp("setup"), fail_once=True)
    return dict(run, spans=tracing.get_spans())


@pytest.mark.parametrize("stage", ("init",) + TRAIN_STAGES + LOOP_STAGES)
def test_a_fit_observes_each_stage_once(setup_traced, stage):
    want = 2 if stage == "first_call" else 1  # the new batch shape's too
    assert setup_traced["setup"].get((stage, "none")) == want, \
        setup_traced["setup"]


@pytest.mark.parametrize("stage", ("init",) + TRAIN_STAGES + LOOP_STAGES)
def test_a_gang_restart_observes_the_train_stages_again(setup_restarted,
                                                        stage):
    want = {"first_call": 2}.get(stage, 2 if stage in TRAIN_STAGES else 1)
    assert setup_restarted["setup"].get((stage, "none")) == want, \
        setup_restarted["setup"]
    assert setup_restarted["spans"] == []  # tracing was off


# name, parent's name (None: a root), thread ("driver": fit()'s caller).
SETUP_TABLE = [
    ("setup::init", None, "driver"),
    ("setup::worker_group", None, "driver"),
    ("setup::backend", None, "driver"),
    ("setup::loop_start", None, LOOP),
    ("setup::mesh", None, LOOP),
    ("setup::state_init", None, LOOP),
    # Every call that found its program; the first call, a root too, and
    # the retracing one inside its ``train::step`` are the recompile test's.
    ("train::step", None, LOOP),
]


@pytest.mark.parametrize("name,parent,thread", SETUP_TABLE)
def test_set_up_yields_the_span(setup_traced, name, parent, thread):
    test_a_save_yields_the_span(setup_traced, name, parent, thread)


def test_the_set_up_spans_carry_their_attributes(setup_traced):
    one = {s.name: s for s in setup_traced["spans"]
           if s.name.startswith("setup::")}
    assert one["setup::worker_group"].attributes == {"workers": 1}
    assert one["setup::loop_start"].attributes == {"rank": 0}
    assert one["setup::mesh"].attributes["devices"] == 1
    assert isinstance(one["setup::mesh"].attributes["backend_started"], bool)
    state = one["setup::state_init"].attributes
    assert state["leaves"] > 10 and state["bytes"] > 1e5
    # In order on the clock in-process readers use.
    starts = [one[n].perf_start for n, _, _ in SETUP_TABLE[:6]]
    assert starts == sorted(starts) and starts[0] > 0


@pytest.mark.parametrize("program,parent", [
    ("init", "setup::state_init"), ("step", "step::first_call")])
def test_a_program_s_compile_hangs_under_its_stage(setup_traced, program,
                                                   parent):
    """JAX names the jitted function ``f`` while it traces it and
    ``jit(f)`` from lowering on."""
    stage = min((s for s in setup_traced["spans"] if s.name == parent),
                key=lambda s: s.perf_start)
    inside = [s for s in setup_traced["spans"]
              if s.name.startswith("compile::") and stage.perf_start - 0.01
              <= s.perf_start <= stage.perf_start + stage.duration]
    # Placed from JAX's own stamps, parented by the thread's active span.
    assert inside and all(s.parent_id == stage.span_id and s.thread == LOOP
                          and s.perf_start + s.duration
                          <= stage.perf_start + stage.duration + 0.01
                          for s in inside)
    own = {s.name: s for s in inside
           if s.attributes["program"] in (program, f"jit({program})")}
    assert set(own) == {"compile::trace", "compile::lower",
                        "compile::backend"}, sorted(own)
    assert own["compile::trace"].perf_start <= \
        own["compile::lower"].perf_start <= own["compile::backend"].perf_start
    assert own["compile::backend"].attributes["cache"] in (
        "hit", "miss", "off")


def test_a_warm_step_reaches_no_listener_and_records_nothing(setup_traced,
                                                             setup_restarted):
    for run in (setup_traced, setup_restarted):
        assert run["metrics"]["warm"] == {"listener_calls": 0, "spans": 0,
                                          "observed": 0}
        # Two steps and an eval-less job built them; JAX holds one each.
        assert run["metrics"]["listeners"] == 1


def test_a_new_batch_shape_is_a_recompile_with_a_journal_row(setup_traced):
    first, again = sorted(
        (s for s in setup_traced["spans"] if s.name == "step::first_call"),
        key=lambda s: s.perf_start)
    assert first.attributes == {"program": "step", "recompile": False}
    assert again.attributes == {"program": "step", "recompile": True}
    # The first call is a root; a later call cannot know that it will
    # retrace, so it is a ``train::step`` with its first_call inside.
    around = setup_traced["by_id"][again.parent_id]
    assert first.parent_id is None and first.thread == LOOP
    assert around.name == "train::step" and around.attributes["n"] == 22
    children = [s for s in setup_traced["spans"]
                if s.parent_id == again.span_id]
    assert {"compile::trace", "compile::backend"} <= {
        s.name for s in children}
    [row] = [r for r in setup_traced["journal"]
             if r[2].get("labels", {}).get("event") == "step_recompile"]
    assert row[0] == "train" and row[1].startswith("step recompiled")


def test_the_compile_series_name_the_stage_they_fell_in(setup_traced):
    seen = setup_traced["compile"]
    for within in ("state_init", "first_call"):
        for phase in ("trace", "lower", "backend"):
            assert seen.get((phase, within), 0) >= 1, seen
    # ``program`` is an attribute of a span, never a label.
    assert all(len(labels) == 2 for labels in seen)


def test_a_stage_inside_another_says_so_and_is_summed_once(tracing_off):
    before = _observations(SETUP)
    with builtin_metrics.setup_stage("init", "setup::init") as span:
        assert span is None and builtin_metrics.setup_stage_open() == "init"
        with builtin_metrics.setup_stage("native_build",
                                         "setup::native_build"):
            assert builtin_metrics.setup_stage_open() == "native_build"
    assert builtin_metrics.setup_stage_open() == "none"
    assert _observed_since(SETUP, before) == {
        ("init", "none"): 1, ("native_build", "init"): 1}


def test_tracing_off_a_stage_is_the_shared_no_op_and_one_observation(
        tracing_off):
    stage = builtin_metrics.setup_stage("mesh", "setup::mesh")
    assert stage._scope is tracing._NO_SPAN
    assert tracing.finished_span_context() is None
    before = _observations(SETUP)
    with stage as span:
        assert span is None
    assert _observed_since(SETUP, before) == {("mesh", "none"): 1}
    assert tracing.get_spans() == []


def test_nested_compile_events_tile(tracing_off):
    """What JAX reports nests: a trace of 10 s that held an inner trace of
    1 s and an eager constant's compile of 2 s is observed as 7 + 1 + 2."""
    from ray_tpu.parallel import compile_events
    from ray_tpu.util import metrics
    trace, backend = (e for e, p in compile_events._PHASE_BY_EVENT.items()
                      if p in ("trace", "backend"))

    def sums():
        for entry in metrics.snapshot():
            if entry["name"] == COMPILE:
                return dict(entry["sums"])
        return {}

    before = sums()
    t = time.time() + 1e6  # after everything this thread has reported
    with builtin_metrics.setup_stage("first_call", "step::first_call"):
        compile_events._on_time_span(trace, t + 1, t + 2, fun_name="add")
        compile_events._on_event("/jax/compilation_cache/cache_hits")
        compile_events._on_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 1.5)
        compile_events._on_time_span(backend, t + 3, t + 5,
                                     fun_name="jit(iota)")
        compile_events._on_time_span(trace, t, t + 10, fun_name="step")
    grew = {k: v - before.get(k, 0.0) for k, v in sums().items()
            if v != before.get(k, 0.0)}
    assert grew == {("trace", "first_call"): pytest.approx(8.0),
                    ("backend", "first_call"): pytest.approx(2.0),
                    ("cache_load", "first_call"): pytest.approx(1.5)}
    tiled = sum(v for (phase, _), v in grew.items() if phase != "cache_load")
    assert tiled == pytest.approx(10.0)  # the whole, each second once


@pytest.mark.parametrize("name,stage", [
    ("setup::init", "train_setup"),
    ("setup::loop_start", "train_setup"),
    ("step::first_call", "train_setup"),
    ("step::lower", "train_setup"),
    ("compile::trace", "compile"),
    ("compile::cache_load", "compile"),
])
def test_the_summary_groups_set_up_spans_by_stage(name, stage):
    assert trace_assembler.span_stage({"name": name}) == stage


def test_grafana_has_a_panel_for_set_up_by_stage():
    from ray_tpu.dashboard.grafana import generate_dashboard
    exprs = [t["expr"] for p in generate_dashboard()["panels"]
             for t in p.get("targets", [])]
    assert any("ray_tpu_train_setup_seconds_sum" in e for e in exprs)


# -- a step says what it waited for ---------------------------------------
# ``compile_events.first_call`` times every call of a wrapped step where it
# is made; ``builtin_metrics.loop_wait`` the loop's waits between steps.

INTERVAL = "ray_tpu_train_step_interval_seconds"
DISPATCH = "ray_tpu_train_step_dispatch_seconds"
LOOP_WAIT = "ray_tpu_train_loop_wait_seconds_total"
STALLED = "ray_tpu_train_step_stalled_seconds_total"


def _series(name):
    from ray_tpu.util import metrics
    for entry in metrics.snapshot():
        if entry["name"] == name:
            return dict(entry.get("series", {}))
    return {}


def _one_device_mesh():
    import jax
    from ray_tpu.parallel import MeshConfig, build_mesh
    return build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                      devices=jax.devices()[:1])


def _wrapped(program="step", after_call=None):
    """A jitted function behind the step's wrapper, as ``make_train_step``
    wraps its own."""
    import jax
    from ray_tpu.parallel import ShardingRules
    from ray_tpu.parallel.train_step import _with_mesh_registered

    def fn(x):
        return x + 1

    fn.__name__ = program
    return _with_mesh_registered(jax.jit(fn), _one_device_mesh(),
                                 ShardingRules(), after_call=after_call)


def _recording_model(seen):
    """The least ``make_train_step`` takes as a model, with a scalar that
    the registry records."""
    import types

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    def loss_fn(params, cfg, tokens, targets, mask):
        loss = (params["w"] * tokens.astype(jnp.float32).mean()).sum()
        return loss, {"loss": loss}

    return types.SimpleNamespace(
        init=lambda cfg, key: {"w": jnp.ones((4,), jnp.float32)},
        param_specs=lambda cfg, rules: {"w": PartitionSpec()},
        loss_fn=loss_fn, RECORDED_METRICS={"loss": seen.append})


@pytest.fixture(scope="module")
def five_calls():
    """A real train step (a model with ``RECORDED_METRICS``) called five
    times with tracing on, then five times with it off, by a loop whose
    own share of an iteration is 50 ms (a sleep): a dispatch, well under
    a millisecond, does not outlast the interval it is compared with
    unless the machine stalls it for fifty."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel.train_step import (init_train_state,
                                             make_train_step)
    mesh = _one_device_mesh()
    seen, out = [], {}
    model = _recording_model(seen)
    tokens = jnp.ones((2, 8), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    for traced in (True, False):
        tracing.clear_spans()
        tracing.set_sample_rate(None)
        (tracing.enable_tracing if traced else tracing.disable_tracing)()
        before = {name: _observations(name) for name in (INTERVAL, DISPATCH)}
        try:
            state = init_train_state(None, mesh, model=model)
            step = make_train_step(None, mesh, model=model)
            for _ in range(5):
                state, metrics = step(state, batch)
                time.sleep(0.05)
            jax.block_until_ready(metrics)
        finally:
            tracing.disable_tracing()
        out[traced] = {
            "spans": [s for s in tracing.get_spans()
                      if s.thread == threading.current_thread().name],
            "observed": {name: _observed_since(name, before[name])
                         for name in before}}
        tracing.clear_spans()
    out["recorded"] = len(seen)
    return out


@pytest.mark.parametrize("name,count", [
    ("step::first_call", 1), ("train::step", 4), ("step::record", 5)])
def test_five_calls_leave_the_spans(five_calls, name, count):
    found = [s for s in five_calls[True]["spans"] if s.name == name]
    assert len(found) == count
    by_id = {s.span_id: s for s in five_calls[True]["spans"]}
    for s in found:
        assert s.duration is not None
        if name == "step::record":  # the call's child, whichever it was
            assert by_id[s.parent_id].name in ("step::first_call",
                                               "train::step")
        else:
            assert s.parent_id is None
    # The newest call's scalars may still be pending when the loop ends.
    assert five_calls["recorded"] in (8, 9, 10)


def test_a_step_s_interval_is_the_difference_of_two_entries(five_calls):
    steps = sorted((s for s in five_calls[True]["spans"]
                    if s.name == "train::step"), key=lambda s: s.perf_start)
    assert [s.attributes["n"] for s in steps] == [2, 3, 4, 5]
    assert all(s.attributes["program"] == "step" for s in steps)
    # The first call made the program: the interval that would span it is
    # set-up's, and the clock starts anew at the second.
    assert "interval_s" not in steps[0].attributes
    for earlier, later in zip(steps, steps[1:]):
        assert later.attributes["interval_s"] == \
            later.perf_start - earlier.perf_start
        assert [later.attributes[k] for k in
                ("save_s", "report_s", "data_s")] == [0.0, 0.0, 0.0]
        # An interval holds the dispatch it starts with: the earlier call
        # had returned when the later began.
        assert earlier.duration <= later.attributes["interval_s"]
        # Self time (less ``step::record``) is the dispatch proper: a small
        # part of a step's interval. (The later call's dispatch is no part
        # of the interval it is compared with, and beside busy neighbours
        # can take as long as a loop that does nothing else gives it: hence
        # the loop's 50 ms an iteration.)
        assert later.duration < later.attributes["interval_s"]


@pytest.mark.parametrize("traced", [True, False])
def test_the_histograms_count_with_tracing_on_and_off(five_calls, traced):
    assert five_calls[traced]["observed"] == {
        DISPATCH: {("step",): 4}, INTERVAL: {("step",): 3}}
    if not traced:
        assert five_calls[False]["spans"] == []


def test_two_wrapped_steps_do_not_mix_their_intervals(tracing_off):
    import jax.numpy as jnp
    tracing.enable_tracing()
    train, evaluate = _wrapped("step"), _wrapped("eval_step")
    x = jnp.zeros(())
    for step in (train, evaluate, train, train, evaluate, train, evaluate):
        step(x)
        time.sleep(0.002)
    for program, calls in (("step", 4), ("eval_step", 3)):
        spans = sorted((s for s in tracing.get_spans()
                        if s.name == "train::step"
                        and s.attributes["program"] == program),
                       key=lambda s: s.perf_start)
        # The first call of each made its program and is no ``train::step``.
        assert [s.attributes["n"] for s in spans] == \
            list(range(2, calls + 1))
        for earlier, later in zip(spans, spans[1:]):
            assert later.attributes["interval_s"] == \
                later.perf_start - earlier.perf_start


def test_the_eval_step_has_a_name_of_its_own():
    from ray_tpu.models import gpt
    from ray_tpu.parallel.train_step import make_eval_step, make_train_step
    mesh = _one_device_mesh()
    cfg = gpt.config("gpt-tiny")
    assert make_eval_step(cfg, mesh).__name__ == "eval_step"
    assert make_train_step(cfg, mesh).__name__ == "step"


def _waits_loop(config):
    """Calls of a wrapped step with, between them, a report, a save, a
    batch, and at last nothing but a sleep."""
    import jax.numpy as jnp
    state = {"params": {"w": jnp.ones((LEAF_ELEMS,), jnp.float32)},
             "step": jnp.int32(0)}
    batches = session.get_dataset_shard("train").iter_jax_batches(
        batch_size=4)
    step, x = _wrapped(), jnp.zeros(())
    for _ in range(8):  # the first makes the program; then a history
        step(x)
    session.report({"i": 0})
    step(x)
    # A save whose stall is surely longer than any stall's floor.
    from ray_tpu.train._internal import sharded_checkpoint as sc
    gather = sc.gather_shard

    def slow_gather(*args, **kwargs):
        time.sleep(0.1)
        return gather(*args, **kwargs)

    sc.gather_shard = slow_gather
    try:
        session.report_sharded({"i": 1}, state, extra={"step": 1})
    finally:
        sc.gather_shard = gather
    step(x)
    next(batches)
    step(x)
    time.sleep(0.2)
    step(x)
    step(x)
    session.report({"done": True})


@pytest.fixture(scope="module")
def waits_run(tmp_path_factory):
    import ray_tpu.data
    from ray_tpu._private import events
    rows, emit = [], events.emit

    def capture(source, message, **kwargs):
        rows.append((source, message, kwargs))
        emit(source, message, **kwargs)

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_tpus=0, _memory=1e9)
    tracing.clear_spans()
    tracing.set_sample_rate(None)
    tracing.enable_tracing()
    before = _series(LOOP_WAIT), _series(STALLED)
    events.emit = capture
    try:
        JaxTrainer(
            _waits_loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                name="waits",
                storage_path=str(tmp_path_factory.mktemp("waits"))),
            datasets={"train": ray_tpu.data.from_numpy(
                [np.arange(64).reshape(16, 4)], column="x")}).fit()
    finally:
        events.emit = emit
        tracing.disable_tracing()
        ray_tpu.shutdown()
    spans = [s for s in tracing.get_spans() if s.thread == LOOP]
    tracing.clear_spans()
    steps = sorted((s for s in spans if s.name == "train::step"),
                   key=lambda s: s.perf_start)
    return {"spans": spans, "steps": {s.attributes["n"]: s for s in steps},
            "waited": {k[0]: v - before[0].get(k, 0.0)
                       for k, v in _series(LOOP_WAIT).items()},
            "stalled": {k: v - before[1].get(k, 0.0)
                        for k, v in _series(STALLED).items()},
            "journal": [r for r in rows if r[2].get("labels", {}).get(
                "event") == "step_stall"]}


# The call after the wait, the wait's part, the span that timed it.
@pytest.mark.parametrize("n,part,span", [
    (9, "report_s", "train::report"),
    (10, "save_s", "train::report_sharded"),
    (11, "data_s", "data::next_batch")])
def test_a_wait_between_two_calls_lands_in_its_part(waits_run, n, part,
                                                    span):
    step = waits_run["steps"][n]
    before = waits_run["steps"][n - 1]
    [timed] = [s for s in waits_run["spans"] if s.name == span
               and s.parent_id is None
               and before.perf_start < s.perf_start < step.perf_start]
    parts = {k: step.attributes[k] for k in ("save_s", "report_s", "data_s")}
    # Two clock reads inside the span's own two.
    assert 0 < parts.pop(part) <= timed.duration
    assert parts == dict.fromkeys(parts, 0.0)  # a save's ack is the save's
    assert step.attributes["interval_s"] > timed.duration


def test_the_waits_feed_their_counter(waits_run):
    waited = waits_run["waited"]
    assert set(waited) == {"report", "save", "data"}
    total = {part: sum(s.attributes.get(part, 0.0)
                       for s in waits_run["steps"].values())
             for part in ("report_s", "save_s", "data_s")}
    assert waited["save"] == pytest.approx(total["save_s"])
    assert waited["data"] == pytest.approx(total["data_s"])
    # The loop's last report comes after the last call.
    assert waited["report"] > total["report_s"] > 0


def test_a_save_is_no_stall_and_a_sleep_is_one(waits_run):
    """The save is the longest interval by far and its rest is a step's
    like any; the sleep is in no part, so it is a stalled row, its cause
    what the runtime's sampler saw meanwhile (``none`` on a quiet
    machine)."""
    save = waits_run["steps"][10].attributes
    slept = waits_run["steps"][12].attributes
    assert save["interval_s"] - save["save_s"] < 0.05
    assert save["save_s"] > 0.1
    assert slept["interval_s"] > 0.2
    stalled = {row[1].split(" took ")[0]: row
               for row in waits_run["journal"]}
    # Call 9 was followed by the save, call 11 by the sleep (a busy machine
    # may stall another of these millisecond steps; never the save's).
    assert "step stalled: step call 9" not in stalled
    row = stalled["step stalled: step call 11"]
    assert row[0] == "train"
    labels = row[2]["labels"]
    assert labels["program"] == "step" and labels["event"] == "step_stall"
    assert labels["cause"] != "unwatched"  # the runtime's sampler runs
    assert sum(waits_run["stalled"].values()) > 0.15
    assert all(key[0] == "step" for key in waits_run["stalled"])


def _planted_agent(late):
    """``global_profiler`` of a process whose sampler has seen what the
    test says it saw."""
    from ray_tpu._private import profiling
    agent = profiling.ProfilerAgent("test", hz=10, start=False)
    agent._late.extend(late)
    return lambda: agent


# (woke, lateness, cause) of the sampler's late ticks; the stalled interval
# is [10.0, 11.5] on a median of 1.0, so 0.5 s over.
@pytest.mark.parametrize("late,want", [
    ([], (0.0, "none")),
    # One before the interval, one across its start, one inside it.
    ([(9.9, 0.3, "gc"), (10.1, 0.3, "runqueue"), (11.0, 0.35, "runqueue")],
     (0.45, "runqueue")),
    ([(10.5, 0.1, "gc"), (11.2, 0.3, "steal")], (0.4, "steal")),
    (None, (0.0, "unwatched")),
])
def test_a_long_interval_is_one_row_with_the_late_ticks_inside_it(
        monkeypatch, late, want):
    from ray_tpu._private import events, profiling
    from ray_tpu.parallel import compile_events
    rows = []
    monkeypatch.setattr(events, "emit",
                        lambda *a, **kw: rows.append((a, kw)))
    monkeypatch.setattr(profiling, "global_profiler",
                        (lambda: None) if late is None
                        else _planted_agent(late))
    before = _series(STALLED)
    clock = compile_events.StepClock("planted")
    entries = [float(t) for t in range(5, 11)] + [11.5, 12.52, 13.5]
    for t in entries:
        interval = clock.enter(t, None)
        if interval is not None:
            clock.judge(*interval)
    # Eight intervals: five of 1.0 s, then 1.5 (stalled), 1.02 (inside the
    # floor of 0.05 s) and 0.98.
    [((source, message), kwargs)] = rows
    assert source == "train" and "call 6 took 1.500s" in message
    assert f"late by {want[0]:.3f}s inside it, cause {want[1]}" in message
    assert kwargs["labels"] == {"event": "step_stall", "program": "planted",
                                "cause": want[1]}
    now = _series(STALLED)
    assert now[("planted", want[1])] - before.get(
        ("planted", want[1]), 0.0) == pytest.approx(0.5)


@pytest.mark.parametrize("name,stage", [
    ("train::step", "train_step"), ("step::record", "train_step"),
    ("host::tick", "host_late")])
def test_the_summary_groups_the_step_and_the_tick_by_stage(name, stage):
    assert trace_assembler.span_stage({"name": name}) == stage
