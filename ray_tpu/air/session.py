"""Train/Tune session: the in-loop API (report, world rank, checkpoint).

Analog of the reference's python/ray/air/session.py:41 (session.report) and
train/_internal/session.py (_TrainSession's bounded result queue). Each train
worker / trial has a _Session bound to its execution context; ``report``
blocks on a size-1 queue until the driver consumes the result — exactly the
reference's backpressure semantics.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Dict, Optional

from ray_tpu._private import builtin_metrics
from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.util import tracing


class StopSession(BaseException):
    """Raised inside report() when the driver stopped this worker/trial
    (e.g. an early-stopping scheduler). Inherits BaseException so user
    ``except Exception`` blocks don't swallow it."""


class _Session:
    def __init__(self, world_rank: int = 0, world_size: int = 1,
                 local_rank: int = 0, trial_id: str = "",
                 trial_name: str = "", config: Optional[dict] = None,
                 checkpoint: Optional[Checkpoint] = None,
                 dataset_shards: Optional[dict] = None,
                 ckpt_ctx: Optional[dict] = None):
        self.world_rank = world_rank
        self.world_size = world_size
        self.local_rank = local_rank
        self.trial_id = trial_id
        self.trial_name = trial_name
        self.config = config or {}
        self.loaded_checkpoint = checkpoint
        self.dataset_shards = dataset_shards or {}
        # Sharded-checkpoint context from the BackendExecutor: the run
        # name, storage URI, and agreed seq base every rank writes its
        # shard files under (see report_sharded).
        self.ckpt_ctx = ckpt_ctx
        # Set by the TrainWorker so a chaos kill fired inside a shard
        # write makes the whole rank play dead, not just the one call.
        self.on_chaos_kill = None
        self._shard_reports = 0
        self._shard_backend = None
        # The save in flight: its writer thread (started by the first save)
        # and what that raised, for the loop's thread to raise again.
        self._writer: Optional[threading.Thread] = None
        self._writer_error: Optional[BaseException] = None
        # Size-1 queue: the worker blocks in report() until the driver drains
        # (reference: train/_internal/session.py:63 queue.Queue(1)).
        self.result_queue: "queue.Queue" = queue.Queue(1)
        self.continue_event = threading.Event()
        self.stop_requested = False
        self.finished = False

    def report(self, metrics: Dict[str, Any],
               checkpoint: Optional[Checkpoint] = None) -> None:
        if self.stop_requested:
            raise StopSession()
        with builtin_metrics.loop_wait("report", "train::report"):
            self.result_queue.put(
                {"metrics": dict(metrics), "checkpoint": checkpoint})
            # The driver's drain: it takes the result and lets us go on.
            with tracing.child_span("train::report_wait"):
                self.continue_event.wait()
            self.continue_event.clear()
        if self.stop_requested:
            raise StopSession()

    def report_sharded(self, metrics: Dict[str, Any], state: Any,
                       specs: Optional[dict] = None,
                       axes_items=None,
                       extra: Optional[Dict[str, Any]] = None) -> None:
        """Report metrics and begin THIS RANK's checkpoint shard.

        Phase one of the two-phase sharded save. The train loop waits
        here only while the state leaves the device: the rank's local
        parameter blocks of ``state`` (per ``specs``; default: dim 0 of
        every array over an ``fsdp`` axis of ``world_size``) are brought
        to host memory, the metrics are reported as by ``report``, and
        this returns. The rank's writer thread then checksums the blocks,
        writes them into one ``.shard-<rank>`` file through the run's
        spill backend, fsyncs and renames it, and hands the shard record
        to the driver as the write's ack, without waiting for another
        report. A failed write acks ``{"error": ...}`` instead — the
        driver fails that save attempt cleanly and training continues from
        the previous checkpoint.

        What holds when. On return ``state`` may be overwritten, donated
        or deleted; the checkpoint does not exist yet. It exists when the
        driver has committed its manifest, which it writes last, once
        every rank has acked that save. ``Result.checkpoint``,
        ``session.get_checkpoint()`` and a restart see committed
        checkpoints only, so a crash before the manifest leaves the
        previous one the newest. A rank has one save in flight: the next
        ``report_sharded`` first waits for this one's writer
        (``ckpt::drain_wait``), and a train function that returns is held
        until its last save is written and acked, so ``fit()`` returns
        with every save committed or failed.
        """
        from ray_tpu._private import chaos, spill
        from ray_tpu.train._internal import sharded_checkpoint as sc
        ctx = self.ckpt_ctx
        if ctx is None:
            raise RuntimeError(
                "report_sharded needs a sharded-checkpoint context: run "
                "under a trainer with RunConfig.storage_path set")
        if self._shard_backend is None:
            self._shard_backend = spill.backend_for_uri(
                ctx["storage_uri"], session_id=ctx.get("session_id", ""))
        run, rank = ctx["run"], self.world_rank
        seq = int(ctx["seq_base"]) + self._shard_reports
        self._shard_reports += 1
        if axes_items is None:
            axes_items = [("fsdp", self.world_size)]
        # The stall's phases are child spans (ckpt::drain_wait, ckpt::meta,
        # gather_shard's prefetch and gather, then a nested train::report);
        # the writer's (write_gathered's copy / checksum / write) are
        # children too, on its thread, and end after this span has.
        with builtin_metrics.loop_wait(
                "save", "train::report_sharded") as span:
            if span is not None:
                span.attributes.update(seq=seq, rank=rank)
            with tracing.child_span("ckpt::drain_wait"):
                self.wait_for_writer()
            with tracing.child_span("ckpt::meta"):
                flat, structure = sc.flatten_tree(state)
                if specs is None:
                    specs = sc.default_specs(flat, axis=axes_items[0][0])
            try:
                blocks = sc.gather_shard(run, seq, rank, flat, specs,
                                         axes_items, detach=True)
            except chaos.ChaosKill:
                if self.on_chaos_kill is not None:
                    self.on_chaos_kill()
                raise
            except spill.SpillFailure as exc:
                # Refused before the first byte: the failure is the ack.
                self._ack({"seq": seq, "rank": rank, "error": str(exc)},
                          metrics)
            else:
                tree_meta = None
                if rank == 0:
                    with tracing.child_span("ckpt::meta"):
                        tree_meta = sc.build_tree_meta(
                            flat, structure, specs, axes_items, extra)
                self._start_writer(span, metrics, seq, blocks, tree_meta)
            # After the ack or the writer's start, so that a stop the
            # report raises finds the save begun: it is then written and
            # acked like any other.
            self.report(metrics)

    def _ack(self, record: dict, metrics: Dict[str, Any]) -> None:
        """Hand a save's shard record to the driver: an item of its own
        in the result queue, which no report waits behind, with the
        metrics of the report that began the save."""
        self.result_queue.put({"ack": record, "metrics": dict(metrics)})

    def _start_writer(self, span, metrics: Dict[str, Any], seq: int,
                      blocks: dict, tree_meta: Optional[dict]) -> None:
        """A save's second half on this rank's writer thread, under the
        save's span: host memory to the shard file, then the ack. What it
        raises other than a failed write is kept for the loop."""
        from ray_tpu._private import chaos, spill
        from ray_tpu.train._internal import sharded_checkpoint as sc
        rank = self.world_rank

        def run():
            try:
                with tracing.adopt_span(span):
                    try:
                        record = sc.write_gathered(
                            self._shard_backend, self.ckpt_ctx["run"], seq,
                            rank, blocks)
                        if tree_meta is not None:
                            record["tree_meta"] = tree_meta
                    except spill.SpillFailure as exc:
                        record = {"seq": seq, "rank": rank,
                                  "error": str(exc)}
                self._ack(record, metrics)
            except BaseException as exc:  # noqa: BLE001 - the loop's to see
                if isinstance(exc, chaos.ChaosKill) and \
                        self.on_chaos_kill is not None:
                    self.on_chaos_kill()
                self._writer_error = exc

        self._writer = threading.Thread(
            target=run, name=f"ckpt-writer-{rank}", daemon=True)
        self._writer.start()

    def wait_for_writer(self) -> None:
        """Block until no save of this rank is in flight: its shard
        written, or failed, and the ack handed to the driver. Raises, once
        and on the caller's thread, what the writer raised."""
        writer, self._writer = self._writer, None
        if writer is not None:
            writer.join()
        error, self._writer_error = self._writer_error, None
        if error is not None:
            raise error


# One session per OS thread: train workers are actor threads, so
# thread-local storage gives each worker its own session.
_local = threading.local()


def _set_session(session: Optional[_Session]) -> None:
    _local.session = session


def _get_session() -> Optional[_Session]:
    return getattr(_local, "session", None)


def _require_session() -> _Session:
    s = _get_session()
    if s is None:
        raise RuntimeError(
            "No session active: this API must be called inside a train loop "
            "or Tune trainable run by JaxTrainer/Tuner.")
    return s


# -- public API (reference: air/session.py) ------------------------------

def report(metrics: Dict[str, Any],
           checkpoint: Optional[Checkpoint] = None) -> None:
    _require_session().report(metrics, checkpoint)


def report_sharded(metrics: Dict[str, Any], state: Any,
                   specs: Optional[dict] = None, axes_items=None,
                   extra: Optional[Dict[str, Any]] = None) -> None:
    """Report metrics + this rank's shard of ``state`` (per-rank sharded
    checkpointing). Returns once the state is in host memory: from then on
    ``state`` may be overwritten or donated, and the rank's writer thread
    writes the shard beside the next steps. The checkpoint exists when its
    manifest is committed, after every rank's writer has acked the save;
    ``Result.checkpoint`` and a restart see committed ones only, and
    ``fit()`` waits for the last (``_Session.report_sharded``)."""
    _require_session().report_sharded(metrics, state, specs=specs,
                                      axes_items=axes_items, extra=extra)


def get_checkpoint() -> Optional[Checkpoint]:
    return _require_session().loaded_checkpoint


def get_world_rank() -> int:
    return _require_session().world_rank


def get_world_size() -> int:
    return _require_session().world_size


def get_local_rank() -> int:
    return _require_session().local_rank


def get_trial_id() -> str:
    return _require_session().trial_id


def get_trial_name() -> str:
    return _require_session().trial_name


def get_config() -> dict:
    return dict(_require_session().config)


def get_dataset_shard(name: str = "train"):
    shard = _require_session().dataset_shards.get(name)
    if shard is None:
        raise KeyError(
            f"No dataset shard named {name!r} was passed to the trainer "
            f"(available: {list(_require_session().dataset_shards)})")
    return shard
