"""BackendExecutor: drives the training gang and streams results.

Analog of the reference's train/_internal/backend_executor.py:43 (start:94
creates the WorkerGroup in a placement group; start_training:315;
get_next_results:414 gathers one result per worker per round). Gang
fault-tolerance is TPU-shaped: a mesh/slice fails as a unit, so recovery
restarts the WHOLE worker group from the latest checkpoint (SURVEY.md §7
hard parts), not one worker.

Failure handling covers both halves of the reference contract
(backend_executor poll loop + TrainingWorkerError gang restart):

* **Application errors** travel inside result payloads and surface as
  ``TrainingFailedError(cause_kind="app")``.
* **System failures** — a worker/daemon that actually dies raises
  ``ActorDiedError``/``NodeDiedError``/… straight out of the gang RPCs
  (``start_training``, ``get_next_result``, ``on_training_start``).
  Every such RPC is wrapped and classified with the shared
  ``ray_tpu.exceptions.is_system_failure`` (same helper as serve
  failover), so a SIGKILLed rank takes the gang-restart path too,
  resuming from ``latest_checkpoint`` — the durable URI checkpoint when
  a ``CheckpointManager`` is attached.
* **Hangs** — the result gather is ``ray_tpu.wait``-based (one dead or
  hung worker can't wedge the round behind rank order), and after
  ``RAY_TPU_train_hang_timeout_s`` without any result every pending
  rank is liveness-probed (``ping``); a failed probe is treated as a
  system failure.

Restarts are **elastic and bounded**: jittered ``Backoff`` between
attempts, a ``RAY_TPU_train_restart_wait_s`` bounded wait for resources,
and ``ScalingConfig.min_workers`` lets the gang come back smaller when
the cluster shrank.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ray_tpu._private import builtin_metrics
from ray_tpu._private.channel import Backoff
from ray_tpu._private.ray_config import runtime_config_value
from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import FailureConfig, ScalingConfig
from ray_tpu.air.result import Result
from ray_tpu.exceptions import RayError, is_system_failure
from ray_tpu.train._internal.worker_group import WorkerGroup
from ray_tpu.train.backend import BackendConfig
from ray_tpu.util import tracing

logger = logging.getLogger("ray_tpu.train")


class TrainingFailedError(RayError):
    """Training failed. ``latest_checkpoint`` carries the newest
    checkpoint reported before the failure (a durable URI checkpoint
    when a storage_path was configured); ``cause_kind`` is ``"system"``
    (infrastructure died / hung) or ``"app"`` (the train loop raised).
    The original failure stays chained as ``__cause__``."""

    def __init__(self, message: str = "",
                 latest_checkpoint: Optional[Checkpoint] = None,
                 cause_kind: str = "app"):
        super().__init__(message)
        self.latest_checkpoint = latest_checkpoint
        self.cause_kind = cause_kind


def _count_gang_restart(cause: str) -> None:
    try:
        from ray_tpu._private import builtin_metrics, events
        builtin_metrics.train_gang_restarts().inc(tags={"cause": cause})
        events.emit("train", f"gang restart ({cause} failure)",
                    severity="warning", labels={"cause": cause})
    except Exception:  # noqa: BLE001 - metrics never break recovery
        pass


class BackendExecutor:
    def __init__(self, backend_config: BackendConfig,
                 scaling_config: ScalingConfig,
                 failure_config: Optional[FailureConfig] = None,
                 result_timeout: Optional[float] = None,
                 checkpoint_manager: Optional[Any] = None):
        self.backend_config = backend_config
        self.backend = backend_config.backend_cls()
        self.scaling_config = scaling_config
        self.failure_config = failure_config or FailureConfig()
        # None = block indefinitely between reports (first steps of large
        # models can spend many minutes in XLA compilation).
        self.result_timeout = result_timeout
        # Persists reported checkpoints through a durable spill backend
        # (train/_internal/checkpoint_manager.py); None keeps the
        # process-local dict/directory behavior.
        self.checkpoint_manager = checkpoint_manager
        self.worker_group: Optional[WorkerGroup] = None
        self._num_workers = scaling_config.num_workers
        # Set by a membership death push: the result gather probes
        # pending ranks immediately instead of waiting out the full
        # train_hang_timeout_s.
        self._node_death = threading.Event()

    def start(self, num_workers: Optional[int] = None) -> None:
        if num_workers is not None:
            self._num_workers = num_workers
        n = self._num_workers
        with builtin_metrics.setup_stage(
                "worker_group", "setup::worker_group") as span:
            if span is not None:
                span.attributes["workers"] = n
            self.worker_group = WorkerGroup(
                n,
                self.scaling_config.worker_resources(),
                self.scaling_config.placement_strategy,
                bundles=self.scaling_config.as_placement_group_bundles()[:n],
                runtime_env=getattr(self.scaling_config, "runtime_env",
                                    None))
        with builtin_metrics.setup_stage("backend", "setup::backend"):
            self.backend.on_start(self.worker_group, self.backend_config)

    def run(self, train_fn: Callable, config: dict, trial_info: dict,
            checkpoint: Optional[Checkpoint] = None,
            dataset_shards_per_worker: Optional[List[dict]] = None,
            result_callback: Optional[Callable[[dict], bool]] = None
            ) -> Result:
        """Run the loop on all workers; returns the final Result.

        result_callback receives each per-round rank-0 metrics dict; if it
        returns False, training is stopped early.

        ``FailureConfig.max_failures``: 0 fails fast (original cause
        chained), N allows N gang restarts, -1 retries forever. Each
        restart resumes from the newest checkpoint reported so far.
        """
        failures_left = self.failure_config.max_failures
        restart_backoff = Backoff(initial=0.5, cap=10.0)
        membership = self._subscribe_membership()
        try:
            while True:
                try:
                    return self._run_once(train_fn, config, trial_info,
                                          checkpoint,
                                          dataset_shards_per_worker,
                                          result_callback)
                except TrainingFailedError as e:
                    latest = getattr(e, "latest_checkpoint", None)
                    if failures_left == 0:
                        raise
                    failures_left -= 1 if failures_left > 0 else 0
                    cause = getattr(e, "cause_kind", "app")
                    _count_gang_restart(cause)
                    logger.warning(
                        "Training failed (%s, cause=%s); gang-restarting "
                        "worker group from %s (%s retries left)", e, cause,
                        latest,
                        "inf" if failures_left < 0 else failures_left)
                    checkpoint = latest or checkpoint
                    self.shutdown()
                    # Jittered pause so N drivers restarting against one
                    # shrunken cluster don't stampede the scheduler.
                    time.sleep(restart_backoff.next())
                    self._restart_elastic()
        finally:
            if membership is not None:
                membership.unsubscribe(self._on_membership_event)

    def _subscribe_membership(self):
        """Subscribe to the head's membership table for node-death
        pushes when the driver runs in the head process. Best effort:
        without it the hang-timeout probe still catches dead ranks."""
        try:
            from ray_tpu._private.worker import global_worker
            membership = getattr(global_worker._runtime, "membership",
                                 None)
        except Exception:  # noqa: BLE001 - no in-process runtime
            return None
        if membership is not None:
            membership.subscribe(self._on_membership_event)
        return membership

    def _on_membership_event(self, event: dict) -> None:
        if event.get("event") == "dead":
            self._node_death.set()

    # -- elastic restart ---------------------------------------------------

    def _placeable_workers(self, desired: int) -> int:
        """How many train workers the cluster could place right now,
        judged by available resources against one worker's demand."""
        import ray_tpu
        try:
            avail = ray_tpu.available_resources()
        except Exception:  # noqa: BLE001 - no introspection: assume full
            return desired
        need = self.scaling_config.worker_resources()
        fits = desired
        for key, per_worker in need.items():
            if per_worker <= 0:
                continue
            fits = min(fits, int(avail.get(key, 0.0) // per_worker))
        return fits

    def _restart_elastic(self) -> None:
        """Re-create the worker group, waiting a bounded
        ``RAY_TPU_train_restart_wait_s`` for the full complement and
        shrinking down to ``ScalingConfig.min_workers`` if the cluster
        cannot place it (e.g. the failed slice has not been replaced)."""
        desired = self.scaling_config.num_workers
        minimum = self.scaling_config.min_workers or desired
        wait_s = float(runtime_config_value("train_restart_wait_s", 30.0))
        deadline = time.monotonic() + max(0.0, wait_s)
        last_exc: Optional[BaseException] = None
        fit = 0
        while True:
            fit = self._placeable_workers(desired)
            # Hold out for the full complement until the deadline; only
            # then settle for an elastic (>= minimum) gang.
            settle = time.monotonic() >= deadline
            if fit >= desired or (settle and fit >= minimum):
                n = desired if fit >= desired else max(minimum, fit)
                if n < desired:
                    logger.warning(
                        "Elastic gang restart with %d/%d workers "
                        "(min_workers=%d): cluster shrank and "
                        "train_restart_wait_s=%ss expired", n, desired,
                        minimum, wait_s)
                try:
                    self.start(num_workers=n)
                    return
                except Exception as exc:  # noqa: BLE001
                    # Raced a node death between sizing and reservation
                    # (the scheduler can refuse the placement group it
                    # just advertised room for). Clean up and re-size.
                    last_exc = exc
                    logger.warning(
                        "gang restart with %d workers failed (%s); "
                        "re-sizing", n, exc)
                    self.shutdown()
            if settle:
                break
            time.sleep(0.25)
        err = TrainingFailedError(
            f"cluster cannot place even min_workers={minimum} train "
            f"workers (room for {fit}) within "
            f"train_restart_wait_s={wait_s}s", cause_kind="system")
        if last_exc is not None:
            err.__cause__ = last_exc
        raise err

    # -- failure classification --------------------------------------------

    def _system_failure(self, exc: BaseException,
                        latest_checkpoint: Optional[Checkpoint]
                        ) -> TrainingFailedError:
        err = TrainingFailedError(
            f"system failure in training gang: "
            f"{type(exc).__name__}: {exc}",
            latest_checkpoint=latest_checkpoint, cause_kind="system")
        err.__cause__ = exc
        return err

    def _probe_liveness(self, ranks: List[int],
                        hang_timeout: float) -> List[int]:
        """Ping every pending rank with a bounded get; any failure
        (dead actor, lost node, probe timeout) marks the rank dead."""
        import ray_tpu
        probe_timeout = max(0.2, min(5.0, hang_timeout))
        refs = {rank: self.worker_group.workers[rank].ping.remote()
                for rank in ranks}
        dead = []
        for rank, ref in refs.items():
            try:
                ray_tpu.get(ref, timeout=probe_timeout)
            except BaseException as exc:  # noqa: BLE001
                logger.warning("liveness probe of train rank %d failed: %s",
                               rank, exc)
                dead.append(rank)
        return dead

    def _drain(self, pending: Dict[Any, int],
               latest_checkpoint: Callable[[], Optional[Checkpoint]],
               on_payload: Callable[[int, Any], None]) -> None:
        """Gather every pending ref with ``ray_tpu.wait`` (no rank-order
        blocking: whichever rank finishes — or dies — first is observed
        first), until ``pending`` is empty: ``on_payload`` may add to it.
        System failures raise ``TrainingFailedError`` with what
        ``latest_checkpoint()`` then names; after
        ``RAY_TPU_train_hang_timeout_s`` with no result, unresponsive
        ranks (failed liveness probe) are treated the same way."""
        import ray_tpu
        hang_timeout = float(
            runtime_config_value("train_hang_timeout_s", 60.0))
        slice_s = min(1.0, hang_timeout / 4.0) if hang_timeout > 0 else 1.0
        last_progress = time.monotonic()
        while pending:
            ready, _ = ray_tpu.wait(list(pending), num_returns=1,
                                    timeout=slice_s)
            if ready:
                last_progress = time.monotonic()
                for ref in ready:
                    rank = pending.pop(ref)
                    try:
                        payload = ray_tpu.get(ref)
                    except BaseException as exc:  # noqa: BLE001
                        if is_system_failure(exc):
                            raise self._system_failure(
                                exc, latest_checkpoint()) from exc
                        raise
                    on_payload(rank, payload)
                continue
            pushed = self._node_death.is_set()
            if pushed:
                self._node_death.clear()
            if pushed or (hang_timeout > 0 and
                          time.monotonic() - last_progress >= hang_timeout):
                # Probe now: either a membership death push arrived (a
                # node this gang may live on was declared dead — no
                # reason to wait out the hang timeout) or the gang has
                # been silent past the timeout.
                dead = self._probe_liveness(sorted(pending.values()),
                                            hang_timeout or 5.0)
                if dead:
                    why = ("a node was declared dead" if pushed else
                           f"no result for {hang_timeout}s")
                    exc = TimeoutError(
                        f"train ranks {dead} failed their liveness "
                        f"probe ({why})")
                    raise self._system_failure(exc, latest_checkpoint())
                # Alive but slow (XLA compile, giant step): keep waiting.
                last_progress = time.monotonic()

    # -- one gang attempt --------------------------------------------------

    def _reshard_accounting(self, checkpoint, new_world: int) -> None:
        """When the gang resumes a sharded checkpoint, record whether
        the mesh changed — and refuse if resharding was disabled."""
        from ray_tpu.train._internal.sharded_checkpoint import \
            ShardedCheckpoint
        if not isinstance(checkpoint, ShardedCheckpoint):
            return
        saved = checkpoint.world_size
        direction = "same" if new_world == saved else \
            ("shrink" if new_world < saved else "grow")
        if direction != "same" and not bool(
                runtime_config_value("train_reshard_on_restart", True)):
            # Deliberately NOT a TrainingFailedError: a config veto must
            # not be retried away by the gang-restart loop.
            raise RuntimeError(
                f"checkpoint seq={checkpoint.seq} was saved on {saved} "
                f"ranks but the gang now has {new_world} and "
                f"train_reshard_on_restart is disabled")
        try:
            from ray_tpu._private import builtin_metrics, events
            builtin_metrics.train_reshards().inc(
                tags={"direction": direction})
            events.emit(
                "train",
                f"resuming sharded checkpoint seq={checkpoint.seq} on "
                f"{new_world} rank(s) (saved on {saved}: {direction})",
                severity="warning" if direction != "same" else "info",
                labels={"event": "reshard", "direction": direction,
                        "saved_world": str(saved),
                        "new_world": str(new_world)})
        except Exception:  # noqa: BLE001 - accounting never breaks resume
            pass

    def _ckpt_ctx(self) -> Optional[dict]:
        """The sharded-save context handed to every rank: run identity,
        storage URI, and the seq base this attempt's saves start at."""
        mgr = self.checkpoint_manager
        if mgr is None:
            return None
        return {"run": mgr.run_name, "storage_uri": mgr.base_uri,
                "session_id": getattr(mgr._backend, "session_id", ""),
                "seq_base": mgr.next_seq_base()}

    def _commit_sharded(self, shard_acks: Dict[int, dict], world: int,
                        metrics: Optional[dict]):
        """Phase two of a sharded save: commit iff EVERY rank acked a
        clean shard write under one agreed seq. Anything less — a rank
        that acked an error, a missing ack, disagreeing seqs — fails
        this save attempt cleanly (the previous committed checkpoint
        still stands) and never writes a manifest. ``metrics`` are those
        of the report that began the save."""
        records = [shard_acks[r] for r in sorted(shard_acks)]
        errors = {r["rank"]: r["error"] for r in records if r.get("error")}
        seqs = {int(r["seq"]) for r in records}
        why = None
        if errors:
            why = f"shard write failed on rank(s) {sorted(errors)}: " \
                  f"{list(errors.values())[0]}"
        elif len(shard_acks) != world:
            why = f"only {len(shard_acks)}/{world} ranks acked a shard"
        elif len(seqs) != 1:
            why = f"ranks disagree on save seq: {sorted(seqs)}"
        elif not any("tree_meta" in r for r in records):
            why = "no rank supplied the tree metadata"
        if why is not None:
            logger.warning("sharded save attempt not committed: %s", why)
            try:
                from ray_tpu._private import builtin_metrics, events
                builtin_metrics.train_checkpoint_persist_failures().inc()
                events.emit("train", f"sharded save aborted: {why}",
                            severity="error",
                            labels={"event": "ckpt_abort",
                                    "seq": str(min(seqs)) if seqs else ""})
            except Exception:  # noqa: BLE001
                pass
            return None
        if self.checkpoint_manager is None:
            logger.warning("sharded save reported but no checkpoint "
                           "manager is attached; dropping")
            return None
        seq = seqs.pop()
        meta = next(r["tree_meta"] for r in records if "tree_meta" in r)
        t0 = time.perf_counter()
        # On the driver's thread, beside the loop: the ranks went on
        # when their state was off the device.
        with tracing.start_span("ckpt::commit") as span:
            if span is not None:
                span.attributes.update(seq=seq, bytes=sum(
                    int(r["bytes"]) for r in records))
            handle = self.checkpoint_manager.register_sharded(
                seq, meta, records, metrics=metrics)
        if handle is not None:
            # Wall time of the save: the slowest rank's shard write
            # plus the manifest commit.
            elapsed = max(float(r.get("write_s", 0.0)) for r in records) \
                + (time.perf_counter() - t0)
            try:
                from ray_tpu._private import builtin_metrics
                builtin_metrics.train_ckpt_save_seconds().observe(elapsed)
            except Exception:  # noqa: BLE001
                pass
        return handle

    def _run_once(self, train_fn, config, trial_info, checkpoint,
                  dataset_shards_per_worker, result_callback) -> Result:
        # Where ``setup::loop_start`` begins; each rank ends it at its train
        # function's first statement, on a thread of its own.
        launched = {"wall": time.time(), "perf": time.perf_counter(),
                    "pid": os.getpid()}
        group = self.worker_group
        latest_checkpoint = checkpoint
        self._reshard_accounting(checkpoint, len(group.workers))
        ckpt_ctx = self._ckpt_ctx()
        try:
            self.backend.on_training_start(group, self.backend_config)
        except BaseException as exc:  # noqa: BLE001
            if is_system_failure(exc):
                raise self._system_failure(exc, latest_checkpoint) from exc
            raise
        starts: Dict[Any, int] = {}
        for rank, worker in enumerate(group.workers):
            shards = (dataset_shards_per_worker[rank]
                      if dataset_shards_per_worker and
                      rank < len(dataset_shards_per_worker) else None)
            starts[worker.start_training.remote(
                train_fn, config, trial_info, checkpoint, shards,
                ckpt_ctx, launched)] = rank
        self._drain(starts, lambda: latest_checkpoint,
                    lambda rank, payload: None)

        world = len(group.workers)
        history: List[Dict[str, Any]] = []
        final_error: Optional[BaseException] = None
        stop_sent = False
        finished = [False] * world
        # Sharded saves between a rank's ack and the commit:
        # {seq: {rank: ack item}}. A rank acks when its writer is done,
        # rounds after the report that began the save and not in the
        # round its peers do, so acks are gathered by seq.
        open_saves: Dict[int, Dict[int, dict]] = {}

        def close_save(seq: int) -> None:
            nonlocal latest_checkpoint
            acks = open_saves.pop(seq)
            committed = self._commit_sharded(
                {rank: item["ack"] for rank, item in acks.items()}, world,
                acks.get(0, {}).get("metrics"))
            if committed is not None:
                latest_checkpoint = committed

        def request(rank: int):
            return group.workers[rank].get_next_result.remote(
                self.result_timeout)

        def on_payload(rank: int, payload: dict) -> None:
            if "ack" not in payload:
                round_payloads[rank] = payload
                return
            # An ack is not the rank's result of this round: ask again,
            # first, so that the rank's next report is taken while the
            # manifest is written.
            pending[request(rank)] = rank
            seq = int(payload["ack"]["seq"])
            open_saves.setdefault(seq, {})[rank] = payload
            if len(open_saves[seq]) == world:
                close_save(seq)

        while not all(finished):
            # Submit one result request to every live worker, then gather
            # via wait — a dead/hung rank 0 can't stall detection of the
            # other ranks' results.
            pending = {request(rank): rank
                       for rank in range(world) if not finished[rank]}
            round_payloads: Dict[int, dict] = {}
            self._drain(pending, lambda: latest_checkpoint, on_payload)
            for rank, payload in round_payloads.items():
                if payload.get("timeout"):
                    final_error = TimeoutError(
                        f"Worker {rank} produced no result within "
                        f"{self.result_timeout}s")
                    finished[rank] = True
                elif payload.get("finished"):
                    finished[rank] = True
                    if payload.get("error") is not None:
                        final_error = payload["error"]
                        logger.error("Worker %d failed:\n%s", rank,
                                     payload.get("traceback", ""))
            if final_error is not None:
                err = TrainingFailedError(
                    str(final_error), latest_checkpoint=latest_checkpoint,
                    cause_kind="app")
                err.__cause__ = final_error
                raise err
            # Persist at most one checkpoint per round (ranks report
            # replicas of the same state; rank 0 is canonical).
            for rank in sorted(round_payloads):
                payload = round_payloads[rank]
                if not payload.get("finished") and \
                        payload.get("checkpoint") is not None:
                    reported = payload["checkpoint"]
                    if self.checkpoint_manager is not None:
                        latest_checkpoint = self.checkpoint_manager.register(
                            reported, payload.get("metrics"))
                    else:
                        latest_checkpoint = reported
                    break
            # Rank 0's stream is canonical for metrics (reference behavior);
            # rounds after rank 0 finishes aren't recorded.
            rank0 = round_payloads.get(0)
            if rank0 is None or rank0.get("finished"):
                continue
            metrics = rank0.get("metrics", {})
            history.append(metrics)
            if result_callback is not None and not stop_sent:
                if result_callback(metrics) is False:
                    stop_sent = True
                    for worker in group.workers:
                        worker.request_stop.remote()
        # Every rank has finished, and a rank finishes only after its last
        # ack: a save still open lacks a rank that never began it.
        for seq in sorted(open_saves):
            close_save(seq)
        return Result(
            metrics=history[-1] if history else {},
            checkpoint=latest_checkpoint,
            metrics_history=history,
            config=config,
            trial_id=trial_info.get("trial_id", ""),
        )

    def shutdown(self) -> None:
        if self.worker_group is not None:
            self.backend.on_shutdown(self.worker_group, self.backend_config)
            self.worker_group.shutdown()
            self.worker_group = None
