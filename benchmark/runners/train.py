"""Runner ``train``: a cell's configuration trained through
``ray_tpu.train.JaxTrainer`` under a traffic file's parameters.

One process. ``ray_tpu.init()`` finds the chips, ``JaxTrainer(...).fit()``
starts one Train worker (a thread of this process) that reserves the cell's
chips, and the loop below is what a user's ``train_loop_per_worker`` is:
``prepare_mesh`` -> ``init_train_state`` -> ``make_train_step``, a batch, a
step, ``session.report``, and every ``save_every`` steps
``session.report_sharded`` of the whole train state. The benchmark adds the
clock, the spans (``bench/<what>``, also written into the profiler's trace),
the reference check before the window and the read-back after it.

Traffic parameters (``traffic/<mix>.json``):

    data          "repeat": one seeded batch made on the device, repeated;
                  "dataset": a fresh batch each step from a seeded
                  ``ray_tpu.data`` dataset given to ``JaxTrainer(datasets=)``
    dataset_rows  rows of seq_len + 1 int32 tokens in that dataset
    report_every  ``session.report`` every this many steps
    save_every    ``session.report_sharded`` every this many steps; 0: never
    num_to_keep   ``CheckpointConfig.num_to_keep``
    ahead_s       seconds of steps sent to the device ahead of the one whose
                  loss the loop waits for (a mix without saves; 0: each
                  step's loss is read before the next is sent)

A unit is one step where nothing is saved and ``save_every`` steps with
their save where something is: rates count whole units only. The window is
``--seconds`` long: an operation (a step with its report, or a step with
its save) starts only if the shortest one of its kind so far would end
inside it, and the window ends at the first that would not. The first of a
kind has nothing to go by and always starts. A ``--trace 1`` run stops
after ``TRACE_UNITS`` whole units if the window holds more, which keeps a
trace of steps of seconds to a few megabytes.

With ``ahead_s`` the loop is the one a user writes who logs a loss late:
steps are sent while the device has less than ``ahead_s`` seconds of them
queued, and the oldest one's loss is read and reported meanwhile, so that
a host that stands still for a second or three (a one-chip machine shares
its host's cores) leaves the device fed. A step is sent only if it is
expected to end inside the window; when none is, nothing more is sent, the
loop waits for all that was sent, and the clock is read after that wait:
every step sent counts, over all of that time.

The runner knows no model module: the program's config, the state and the
step, its forward and loss, and the check of its widths against the
published keys come from ``families/<family>.py``, named by the
configuration's ``program.family``; the plain reference from
``reference/<family>.py``, named by ``reference.family``.

The record this returns is what the metric readers take; see ``run``.
"""

from __future__ import annotations

import math
import os
import shutil
import sys
import tempfile
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import replace
from typing import Any, Dict, List

import harness

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_misses"}
SAVE_SECONDS = "ray_tpu_train_ckpt_save_seconds"
SAVE_FAILURES = "ray_tpu_train_checkpoint_persist_failures"
TRACE_UNITS = 8
#: A read of a loss that waited this long found its step still running, so
#: the read's end is the step's end on the device.
BLOCKED_S = 1e-3
COMMIT_WAIT_S = 60.0


class Counters:
    """Programs JAX made (compiled or loaded from the persistent cache) and
    the cache's hits and misses, from ``jax.monitoring``. One per process:
    JAX offers no way to take a listener off again."""

    def __init__(self) -> None:
        self.counts = {"programs": 0, "cache_hits": 0, "cache_misses": 0}
        self.compile_s = 0.0

    def install(self) -> None:
        import jax

        def on_duration(event: str, duration: float, **_) -> None:
            if event == COMPILE_EVENT:
                self.counts["programs"] += 1
                self.compile_s += duration

        def on_event(event: str, **_) -> None:
            if event in CACHE_EVENTS:
                self.counts[CACHE_EVENTS[event]] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> Dict[str, float]:
        return dict(self.counts, compile_s=self.compile_s)


class Spans:
    """Host spans of the loop on ``time.perf_counter``; while a trace is
    being recorded each is also a ``TraceAnnotation`` ``bench/<name>`` so
    that the device's idle gaps can be attributed on the trace's clock."""

    def __init__(self) -> None:
        self.spans: Dict[str, List[List[float]]] = {}
        self.tracing = False

    @contextmanager
    def __call__(self, name: str):
        import jax
        annotation = jax.profiler.TraceAnnotation("bench/" + name) \
            if self.tracing else None
        if annotation is not None:
            annotation.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.setdefault(name, []).append(
                [t0, time.perf_counter()])
            if annotation is not None:
                annotation.__exit__(None, None, None)


def _histogram(name: str) -> Dict[str, float]:
    """Sum and count of one of the program's histograms, all series."""
    from ray_tpu.util import metrics
    for entry in metrics.snapshot():
        if entry["name"] == name:
            if "sums" in entry:
                return {"sum": sum(entry["sums"].values()),
                        "count": sum(entry["counts"].values())}
            return {"sum": sum(entry["series"].values()), "count": 0}
    return {"sum": 0.0, "count": 0}


def _save_seconds(saves: float) -> Dict[str, float]:
    """The program's save histogram once it holds ``saves`` observations:
    the driver observes a save when it commits the manifest, which since
    the write runs behind the loop is 7-8 s after ``report_sharded`` has
    returned, and 11-17 s where an fsync is slow: ``COMMIT_WAIT_S`` is a
    slow disk's room. A run without saves returns at once."""
    deadline = time.perf_counter() + COMMIT_WAIT_S
    while True:
        seen = _histogram(SAVE_SECONDS)
        if seen["count"] >= saves or time.perf_counter() > deadline:
            return seen
        time.sleep(0.01)


def _bitsums(tree):
    """One exact checksum per leaf, on the device: the leaf's bits summed
    as unsigned 32-bit integers (wrapping), so any changed bit of any
    element changes it and the order of summation does not."""
    import jax
    import jax.numpy as jnp
    out = []
    for leaf in jax.tree.leaves(tree):
        width = jnp.dtype(leaf.dtype).itemsize * 8
        bits = jax.lax.bitcast_convert_type(
            leaf, {8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32}[width])
        out.append(jnp.sum(bits.astype(jnp.uint32), dtype=jnp.uint32))
    return out


def _reference_check(published: Dict[str, Any], family, cfg, mesh, params,
                     seq: int, seed: int) -> Dict[str, Any]:
    """The program's own forward and loss on the parameters as they sit on
    the device against ``reference/<family>.py``, on seeded sequences: the
    logits at a seeded sample of positions (always with the last), measured
    against the RMS of the reference's logits, and the loss per sequence.
    The loss alone proves little: at a random start it sits near ln(vocab)
    whatever the model computes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.parallel import mesh as mesh_mod
    ref_spec = published["reference"]
    reference = harness.load_module("reference", ref_spec["family"])
    rng = np.random.default_rng(seed)
    n_seq, n_pos = ref_spec["sequences"], ref_spec["positions"]
    rows = rng.integers(0, family.vocab_size(cfg), (n_seq, seq + 1),
                        dtype=np.int32)
    where = jnp.asarray(np.sort(np.stack([
        np.append(rng.choice(seq - 1, n_pos - 1, replace=False), seq - 1)
        for _ in range(n_seq)]), axis=-1).astype(np.int32))
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])

    def program_forward(params, tokens, targets, positions):
        logits, losses = family.logits_and_losses(params, cfg, tokens,
                                                  targets)
        sampled = jnp.take_along_axis(
            logits, positions[..., None], axis=1).astype(jnp.float32)
        return sampled, losses

    # The flash kernels read the ambient mesh, as inside a train step.
    previous = mesh_mod.current_mesh()
    mesh_mod.set_current_mesh(mesh)
    try:
        got_logits, got_loss = jax.jit(program_forward)(
            params, tokens, targets, where)
    finally:
        mesh_mod.set_current_mesh(previous)
    want_logits, want_loss, want_rms = reference.forward(
        params, tokens, targets, where, **reference.arguments(published))
    diff = np.asarray(got_logits, np.float64) - np.asarray(want_logits,
                                                           np.float64)
    loss_diff = np.asarray(got_loss, np.float64) - np.asarray(want_loss,
                                                              np.float64)
    rms = float(want_rms)
    check = {
        "logit_rms_err": float(np.sqrt((diff ** 2).mean())) / rms,
        "logit_max_err": float(np.abs(diff).max()) / rms,
        "loss_err": float(np.abs(loss_diff).max()),
        "ref_logit_rms": rms,
        "ref_loss": [float(x) for x in np.asarray(want_loss)],
        "program_loss": [float(x) for x in np.asarray(got_loss)],
    }
    check["ok"] = bool(
        check["logit_rms_err"] <= ref_spec["logit_rms_tol"]
        and check["logit_max_err"] <= ref_spec["logit_max_tol"]
        and check["loss_err"] <= ref_spec["loss_tol"])
    return check


def train_loop(config: Dict[str, Any]) -> None:
    """The Train worker's loop. Everything it learns goes back through
    ``session.report``, as a user's metrics do."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.air import session
    from ray_tpu.parallel import MeshConfig
    from ray_tpu.train import prepare_mesh

    cell, traffic = config["config"], config["traffic"]
    layout, program = cell["layout"], cell["program"]
    seed, counters, spans = config["seed"], config["counters"], Spans()
    batch_size, seq = layout["batch"], layout["seq_len"]
    save_every = int(traffic.get("save_every", 0))
    report_every = int(traffic.get("report_every", 1))
    ahead_s = float(traffic.get("ahead_s", 0.0))
    if ahead_s and (save_every or traffic["data"] != "repeat"):
        raise ValueError("ahead_s is for a mix that repeats one batch and "
                         "saves nothing")
    # From ``fit()`` to here: the trainer starting its worker.
    setup: Dict[str, Any] = {
        "trainer_start_s": time.perf_counter() - config["t_fit"]}

    # -- the product path: mesh, state, step ------------------------------
    t0 = time.perf_counter()
    family = harness.load_module("families", program["family"])
    mesh = prepare_mesh(MeshConfig(**layout["mesh"]))
    cfg = family.config(program)
    vocab = family.vocab_size(cfg)
    problems = family.problems(cell, cfg)
    setup["mesh_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, step = family.state_and_step(cfg, mesh, program, seed)
    jax.block_until_ready(state)
    setup["state_s"] = time.perf_counter() - t0

    # -- the reference check, before anything is timed --------------------
    t0 = time.perf_counter()
    ref_check = _reference_check(cell, family, cfg, mesh, state["params"],
                                 seq, seed + 2)
    setup["reference_s"] = time.perf_counter() - t0

    # -- data -----------------------------------------------------------
    t0 = time.perf_counter()
    seen: List[int] = []
    if traffic["data"] == "repeat":
        toks = np.random.default_rng(seed).integers(
            0, vocab, (batch_size, seq + 1), dtype=np.int32)
        fixed = {"tokens": jnp.asarray(toks[:, :-1]),
                 "targets": jnp.asarray(toks[:, 1:])}

        def next_batch():
            return fixed
    else:
        weights = jnp.arange(1, seq + 2, dtype=jnp.uint32)[None, :]

        @jax.jit
        def split(rows):
            print_ = (rows.astype(jnp.uint32) * weights).sum(
                dtype=jnp.uint32)
            return {"tokens": rows[:, :-1], "targets": rows[:, 1:]}, print_

        batches = session.get_dataset_shard("train").iter_jax_batches(
            batch_size=batch_size, dtypes={"tokens": np.int32},
            device=mesh.devices.flat[0] if mesh.size == 1 else None)

        def next_batch():
            # The fingerprint is read back, so the span holds the wait for
            # the iterator and the transfer to the device.
            batch, fingerprint = split(next(batches)["tokens"])
            seen.append(int(fingerprint))
            return batch

    setup["data_s"] = time.perf_counter() - t0

    # -- one step, with its report or, where one is due, its save ---------
    losses: List[float] = []
    saves: List[Dict[str, Any]] = []
    state_sums = jax.jit(_bitsums)
    n_steps = 0

    def one_step(save: bool) -> None:
        nonlocal state, n_steps
        with spans("data"):
            batch = next_batch()
        with spans("step"):
            state, metrics = step(state, batch)
            jax.block_until_ready(metrics)
            loss = float(metrics["loss"])
        losses.append(loss)
        n_steps += 1
        t = spans.spans["step"][-1]
        report = {"step": n_steps, "loss": loss, "step_s": t[1] - t[0]}
        if save:
            with spans("checksum"):
                jax.block_until_ready(state)
                sums = [int(x) for x in state_sums(state)]
            with spans("save"):
                session.report_sharded(report, state,
                                       extra={"step": n_steps})
            saves.append({"step": n_steps, "sums": sums})
        elif n_steps % report_every == 0:
            with spans("report"):
                session.report(report)

    # -- the same, with steps sent ahead of the loss that is waited for ---
    pending: deque = deque()
    ahead: Dict[str, Any] = {"ahead_s": ahead_s, "step_s": None,
                             "free_at": 0.0, "done_at": None,
                             "between": [], "max_pending": 0}

    def send() -> None:
        nonlocal state
        with spans("data"):
            batch = next_batch()
        with spans("step"):
            state, metrics = step(state, batch)
        pending.append((spans.spans["step"][-1][0], metrics))
        ahead["max_pending"] = max(ahead["max_pending"], len(pending))

    def retire() -> None:
        """The oldest step sent: its loss read back and reported."""
        nonlocal n_steps
        sent_at, metrics = pending.popleft()
        with spans("wait"):
            loss = float(metrics["loss"])
        t0, t1 = spans.spans["wait"][-1]
        # Where the read waited, it ended with the step, and the device
        # then has ``len(pending)`` steps left. Two such ends in a row are
        # one step apart: their median is what a step is expected to take
        # (the first step, sent to an idle device, until there is one).
        if t1 - t0 >= BLOCKED_S:
            if ahead["done_at"] is not None:
                ahead["between"].append(t1 - ahead["done_at"])
                ahead["step_s"] = harness.median(ahead["between"])
            elif ahead["step_s"] is None:
                ahead["step_s"] = t1 - sent_at
            ahead["done_at"] = t1
            ahead["free_at"] = t1 + len(pending) * ahead["step_s"]
        else:
            ahead["done_at"] = None
        losses.append(loss)
        n_steps += 1
        if n_steps % report_every == 0:
            with spans("report"):
                session.report({"step": n_steps, "loss": loss,
                                "step_s": ahead["step_s"]})

    # -- warm-up: every shape the window uses, and nothing else -----------
    t0 = time.perf_counter()
    before = counters.snapshot()
    one_step(save=False)
    setup["first_step_s"] = time.perf_counter() - t0
    setup["first_step_counters"] = {
        k: v - before[k] for k, v in counters.snapshot().items()}
    if save_every:
        # The checksum's program. The save itself compiles nothing, and a
        # run's first stalls the loop like its later ones (1.3-1.8 % longer
        # on the v5e, inside the spread between runs: PERF.md, PR 22), so
        # no save is spent on warming up.
        jax.block_until_ready(state_sums(state))
    setup["warmup_s"] = time.perf_counter() - t0
    warm_steps = n_steps

    # -- the window -------------------------------------------------------
    tracing = bool(config["trace_dir"])
    max_units = TRACE_UNITS if tracing else None
    steps_in_unit = save_every or 1
    # Shortest operation of the window so far, a step with its save apart
    # from a step with its report: what the next one is expected to take.
    shortest: Dict[bool, float] = {}
    if tracing:
        import xplane
        jax.profiler.start_trace(config["trace_dir"],
                                 profiler_options=xplane.trace_options())
        spans.tracing = True
    in_window = {k: -v for k, v in counters.snapshot().items()}
    save_hist0 = _histogram(SAVE_SECONDS)
    spans.spans = {}
    unit_ends: List[float] = []
    window_t0 = time.perf_counter()
    window_end = window_t0 + config["seconds"]

    def whole_unit() -> bool:
        for i in range(steps_in_unit):
            save = bool(save_every) and i == steps_in_unit - 1
            t0 = time.perf_counter()
            if t0 + shortest.get(save, 0.0) >= window_end:
                return False
            one_step(save)
            shortest[save] = min(shortest.get(save, math.inf),
                                 time.perf_counter() - t0)
        return True

    def window_ahead() -> None:
        """The window of a mix with ``ahead_s``. Until a step has been
        timed nothing is known of the queue, and one step is out at a
        time. A loss that has arrived is read and reported at once; one
        that has not is waited for only where the queue is full."""
        sent = 0
        while max_units is None or sent < max_units:
            while pending and pending[0][1]["loss"].is_ready():
                retire()
                unit_ends.append(time.perf_counter())
            now = time.perf_counter()
            step_s = ahead["step_s"]
            if step_s is not None:
                # What is left of the queue: the steps whose loss has not
                # arrived, the oldest of them begun (a program that holds
                # its own call back until the step before has ended keeps
                # the queue shorter than this loop would).
                left = len(pending)
                ahead["free_at"] = min(
                    max(ahead["free_at"], now + max(left - 1, 0) * step_s),
                    now + left * step_s)
            starts = max(now, ahead["free_at"])
            if starts + (step_s or 0.0) >= window_end:
                break
            # One step behind the one that runs is always allowed, or a
            # step longer than half of ``ahead_s`` would be sent to an idle
            # device every time.
            if pending and (step_s is None or (
                    len(pending) >= 2 and starts + step_s - now > ahead_s)):
                retire()
                unit_ends.append(time.perf_counter())
                continue
            send()
            sent += 1
            ahead["free_at"] = starts + (step_s or 0.0)
        while pending:
            retire()
            unit_ends.append(time.perf_counter())

    try:
        with spans("window"):
            if ahead_s:
                window_ahead()
            else:
                while (max_units is None or len(unit_ends) < max_units) \
                        and whole_unit():
                    unit_ends.append(time.perf_counter())
    finally:
        if tracing:
            spans.tracing = False
            jax.profiler.stop_trace()
    window_t1 = time.perf_counter()
    for k, v in counters.snapshot().items():
        in_window[k] += v
    save_hist1 = _save_seconds(save_hist0["count"] + len(saves))

    # -- after the window -------------------------------------------------
    shape = jax.ShapeDtypeStruct((batch_size, seq), jnp.int32)
    lowered = step.lower(state, {"tokens": shape, "targets": shape}).as_text()
    devices = list(mesh.devices.flat)
    memory = [d.memory_stats() or {} for d in devices]
    session.report({"record": {
        "problems": problems,
        "setup": dict(setup, setup_s=window_t0 - config["t_start"]),
        "reference": ref_check,
        "losses": losses,
        "warm_steps": warm_steps,
        "window": {
            "t0": window_t0, "t1": window_t1, "unit_ends": unit_ends,
            "steps_per_unit": steps_in_unit,
            "tokens_per_step": batch_size * seq,
            "steps": n_steps - warm_steps,
            "saves": len(saves),
            "ahead": {k: ahead[k] for k in ("ahead_s", "step_s",
                                            "max_pending")},
        },
        "spans": spans.spans,
        "in_window": in_window,
        "save_seconds": {"sum": save_hist1["sum"] - save_hist0["sum"],
                         "count": save_hist1["count"] - save_hist0["count"]},
        "saves": saves,
        "seen_batches": seen,
        "device_path": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "device_ids": sorted(d.id for d in devices),
            "kernel_calls": lowered.count("tpu_custom_call"),
        },
        "memory_stats": memory,
        "model": {"seq_len": seq, "vocab_size": vocab,
                  "param_bytes": sum(
                      leaf.nbytes for leaf in jax.tree.leaves(
                          state["params"]))},
    }})


def _dataset(seed: int, rows: int, row_len: int, vocab: int):
    """A seeded dataset of token rows, in eight blocks."""
    import numpy as np

    import ray_tpu.data
    tokens = np.random.default_rng(seed + 3).integers(
        0, vocab, (rows, row_len), dtype=np.int32)
    return ray_tpu.data.from_numpy(np.array_split(tokens, 8),
                                   column="tokens")


def _read_back(checkpoint, cell_config: Dict[str, Any], saves, devices
               ) -> Dict[str, Any]:
    """The newest committed checkpoint through the product's restore path,
    against the checksums taken on the device when that state was saved."""
    import jax

    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.train import ShardedCheckpoint
    if not isinstance(checkpoint, ShardedCheckpoint):
        return {"ok": False, "why": f"no sharded checkpoint: {checkpoint!r}"}
    step = checkpoint.extra.get("step")
    saved = {s["step"]: s["sums"] for s in saves}
    if step not in saved:
        return {"ok": False, "why": f"checkpoint of step {step}, saves at "
                                    f"{sorted(saved)}"}
    t0 = time.perf_counter()
    mesh = build_mesh(MeshConfig(**cell_config["layout"]["mesh"]),
                      devices=devices)
    restored = checkpoint.restore_on_mesh(mesh)
    sums = [int(x) for x in jax.jit(_bitsums)(restored)]
    return {"ok": sums == saved[step], "step": step, "leaves": len(sums),
            "newest_save": max(saved), "restore_s": time.perf_counter() - t0}


def run(cell, args) -> Dict[str, Any]:
    """Run one cell once; returns the record the metric readers take:

    ``cell`` (name, chips, config, traffic), ``device`` (platform, kind,
    count, memory_peak_bytes), ``correct`` with ``checks`` (each a bool),
    ``attempted`` and ``failed`` (steps and saves inside the window),
    ``setup`` (seconds of each part, ``setup_s``, cache hits and misses),
    ``window`` (t0, t1, unit_ends, steps and saves begun inside it,
    steps_per_unit, tokens_per_step),
    ``spans`` ({name: [[t0, t1], ...]} inside the window), ``in_window``
    (programs made and cache traffic inside the window), ``save_seconds``
    (the program's own histogram over the window), ``memory_stats``,
    ``trace`` (``xplane.reduce_trace`` of a ``--trace 1`` run, else None).
    """
    import ray_tpu
    traffic, chips = cell.traffic, cell.chips
    t0 = time.perf_counter()
    # Set-up by part, for the diagnostics line: what ran before this.
    parts = {"start_s": t0 - args.t_start}
    if args.rehearsal:
        ray_tpu.init(num_tpus=chips)
    else:
        ray_tpu.init()
    parts["init_s"] = time.perf_counter() - t0
    storage = trace_dir = None
    try:
        t0 = time.perf_counter()
        import jax
        devices = jax.devices()
        parts["devices_s"] = time.perf_counter() - t0
        if not args.rehearsal:
            if devices[0].platform != "tpu":
                sys.exit(f"the benchmark needs a TPU; JAX found "
                         f"{len(devices)} x {devices[0].platform}")
            if len(devices) < chips:
                sys.exit(f"cell {cell.name} needs {chips} chips; JAX found "
                         f"{len(devices)}")
            if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                jax.config.update("jax_compilation_cache_dir",
                                  os.path.join(ROOT, ".jax_cache"))
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
        counters = Counters()
        counters.install()

        from ray_tpu.air import CheckpointConfig, RunConfig, ScalingConfig
        from ray_tpu.train import JaxTrainer
        layout = cell.config["layout"]
        datasets = None
        t0 = time.perf_counter()
        if traffic["data"] == "dataset":
            family = harness.load_module(
                "families", cell.config["program"]["family"])
            datasets = {"train": _dataset(
                args.seed, int(traffic["dataset_rows"]),
                layout["seq_len"] + 1, family.vocab_size(
                    family.config(cell.config["program"])))}
        parts["dataset_s"] = time.perf_counter() - t0
        run_config = None
        if traffic.get("save_every"):
            # Checkpoints go to the machine's local disk, outside the
            # checkout, under TMPDIR, and are removed at exit.
            storage = tempfile.mkdtemp(prefix="bench-ckpt-")
            run_config = RunConfig(
                name="bench", storage_path=storage,
                checkpoint_config=CheckpointConfig(
                    num_to_keep=int(traffic.get("num_to_keep", 1))))
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        failures0 = _histogram(SAVE_FAILURES)["sum"]
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "config": cell.config, "traffic": traffic,
                "seed": args.seed, "seconds": args.seconds,
                "trace_dir": trace_dir, "t_start": args.t_start,
                "t_fit": time.perf_counter(), "counters": counters},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         tpus_per_worker=chips),
            run_config=run_config, datasets=datasets).fit()
        record = result.metrics_history[-1]["record"]
        failed_saves = int(_histogram(SAVE_FAILURES)["sum"] - failures0)

        read_back = None
        if traffic.get("save_every"):
            read_back = _read_back(result.checkpoint, cell.config,
                                   record["saves"], devices[:chips])
        trace = None
        if trace_dir:
            import xplane
            path = xplane.find_xplane(trace_dir)
            if path:
                record["trace_bytes"] = os.path.getsize(path)
                trace = xplane.reduce_trace(xplane.load(path))
    finally:
        ray_tpu.shutdown()
        for path in (storage, trace_dir):
            if path:
                shutil.rmtree(path, ignore_errors=True)

    losses, window = record["losses"], record["window"]
    path, vocab = record["device_path"], record["model"]["vocab_size"]
    warm = record["warm_steps"]
    checks = {
        "widths_as_published": not record["problems"],
        "reference": record["reference"]["ok"],
        "losses_finite": all(math.isfinite(x) for x in losses),
        "loss_near_uniform": abs(losses[1] - math.log(vocab)) < 1.0,
        "no_compile_in_window": record["in_window"]["programs"] == 0,
        "units_completed": len(window["unit_ends"]) > 0,
        "device_path": (args.rehearsal or (
            path["platform"] == "tpu" and path["kernel_calls"] > 0))
        and len(set(path["device_ids"])) == chips,
    }
    if traffic["data"] == "repeat":
        checks["loss_falls"] = losses[-1] < losses[0]
    else:
        seen = record["seen_batches"]
        checks["fresh_batches"] = len(set(seen)) == len(seen) == len(losses)
    if read_back is not None:
        checks["checkpoint_reads_back"] = read_back["ok"]
        checks["saves_committed"] = failed_saves == 0 and \
            window["saves"] > 0
    non_finite = sum(not math.isfinite(x) for x in losses[warm:])
    memory = record["memory_stats"]
    # Two fields account for what a chip holds: live buffers, and the arena
    # reserved for programs' temporaries, which stays reserved between
    # steps. (On the v5e: bytes_in_use + bytes_reserved + the largest free
    # block = bytes_limit to within 1 %.) Their peaks summed are the peak.
    peak = max((m.get("peak_bytes_in_use", 0)
                + m.get("peak_bytes_reserved", 0) for m in memory), default=0)
    setup = dict(record["setup"], **parts)
    return {
        "cell": {"name": cell.name, "chips": chips, "config": cell.config,
                 "traffic": traffic},
        "device": {"platform": path["platform"], "kind": path["kind"],
                   "count": len(path["device_ids"]),
                   "memory_peak_bytes": int(peak)},
        "correct": all(checks.values()),
        "checks": checks,
        "attempted": window["steps"] + window["saves"],
        "failed": non_finite + (failed_saves if read_back else 0),
        "setup": setup,
        "window": window,
        "spans": record["spans"],
        "in_window": record["in_window"],
        "counters": counters.snapshot(),
        "save_seconds": record["save_seconds"],
        "memory_stats": memory,
        "reference": record["reference"],
        "read_back": read_back,
        "losses": losses,
        "device_path": path,
        "model": record["model"],
        "trace": trace,
        "trace_bytes": record.get("trace_bytes"),
    }


# -- rehearsals (rehearse.py): no chip, no number ---------------------------

def shrink(cell):
    """The cell at a tiny size for a run on the CPU (the family's ``tiny``
    configuration, a short dataset, loose tolerances): same code path, same
    layout, same traffic; nothing it measures means anything."""
    family = harness.load_module("families",
                                 cell.config["program"]["family"])
    config = family.tiny(cell.config)
    # Interpreted kernels and a float32 reference on tiny widths: loose.
    config["reference"] = dict(config["reference"], positions=16,
                               logit_rms_tol=0.1, logit_max_tol=0.5,
                               loss_tol=0.05)
    traffic = dict(cell.traffic)
    if traffic.get("dataset_rows"):
        traffic["dataset_rows"] = 256
    return replace(cell, config=config, traffic=traffic)


def compile_for(cell, devices) -> Dict[str, Any]:
    """The cell's real step compiled for described devices (a ``v5e:2x2``
    topology): what the chip's compiler says of its memory, collectives and
    kernels, with nothing run. One program, not what else the process
    holds."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel import MeshConfig, build_mesh
    layout, program = cell.config["layout"], cell.config["program"]
    family = harness.load_module("families", program["family"])
    mesh = build_mesh(MeshConfig(**layout["mesh"]), devices=list(devices))
    cfg = family.config(program)
    state, step = family.abstract_state_and_step(cfg, mesh, program)
    tokens = jax.ShapeDtypeStruct(
        (layout["batch"], layout["seq_len"]), jnp.int32,
        sharding=family.batch_sharding(mesh))
    t0 = time.perf_counter()
    compiled = step.lower(state, {"tokens": tokens,
                                  "targets": tokens}).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    return {
        "compile_s": time.perf_counter() - t0,
        "problems": family.problems(cell.config, cfg),
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "per_device_bytes": mem.argument_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
        + mem.temp_size_in_bytes,
        "kernel_calls": text.count("tpu_custom_call"),
        "collectives": {op: text.count(f" {op}(") + text.count(
            f" {op}-start(") for op in (
                "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")},
    }
