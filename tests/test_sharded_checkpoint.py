"""Sharded, crash-safe, reshardable train checkpoints (ISSUE 20).

Covers the whole two-phase-commit contract: per-rank shard writes
through the spill backends with the rank-0 manifest written last as the
commit record, uncommitted shard sets invisible to ``latest()`` and
garbage-collected on the next index load, checksum rejection of corrupt
shards, chaos ``io_oserror`` on a shard write failing that save attempt
cleanly, a SIGKILLed-rank-mid-save gang restart that resumes the last
committed checkpoint, elastic shrink (8 -> 4) resuming via reshard with
numerically identical parameters, ``num_to_keep`` pruning that removes
manifest + all shards, the mock-s3 backend, and the new config knobs.
"""

import os
import queue
import random
import sys
import threading
import time
import zlib

import cloudpickle
import numpy as np
import pytest

import ray_tpu

# Actor threads may unpickle these train loops outside the tests/
# package — ship this module by value (same idiom as the other train
# suites).
cloudpickle.register_pickle_by_value(sys.modules[__name__])

from ray_tpu._private import builtin_metrics, chaos, events, spill  # noqa: E402
from ray_tpu.air import (CheckpointConfig, FailureConfig, RunConfig,  # noqa: E402
                         ScalingConfig, session)
from ray_tpu.train import DataParallelTrainer, ShardedCheckpoint  # noqa: E402
from ray_tpu.train._internal import sharded_checkpoint as sc  # noqa: E402
from ray_tpu.train._internal.backend_executor import (  # noqa: E402
    BackendExecutor, TrainingFailedError)
from ray_tpu.train._internal.checkpoint_manager import (  # noqa: E402
    CheckpointManager)
from ray_tpu.train.backend import BackendConfig  # noqa: E402
from ray_tpu.util import tracing  # noqa: E402


def _counter_total(counter, tag_substr=None):
    if tag_substr is None:
        return sum(counter.series().values())
    return sum(v for k, v in counter.series().items()
               if any(tag_substr in str(part) for part in k))


def _set_flag(name, value):
    from ray_tpu._private.worker import global_worker
    global_worker._runtime.config.set(name, value)


def _state_at(step):
    """Deterministic full training state as a function of the step —
    every rank can recompute it, so restores are checkable exactly."""
    base = np.arange(13 * 4, dtype=np.float32).reshape(13, 4)
    return {"w": base * float(step + 1),
            "b": np.full((7,), float(step), np.float32),
            "opt": [np.ascontiguousarray(base.T) / float(step + 1),
                    np.float32(step)]}


def _trees_equal(a, b):
    fa, _ = sc.flatten_tree(a)
    fb, _ = sc.flatten_tree(b)
    if set(fa) != set(fb):
        return False
    return all(np.array_equal(np.asarray(fa[p]), np.asarray(fb[p]))
               for p in fa)


def _save_sharded(backend, run, seq, state, world, extra=None):
    """Write all shards + commit a manifest directly (no gang)."""
    flat, structure = sc.flatten_tree(state)
    axes = [("fsdp", world)]
    specs = sc.default_specs(flat)
    records = [
        sc.write_shard(backend, run, seq, rank, flat, specs, axes)
        for rank in range(world)
    ]
    meta = sc.build_tree_meta(flat, structure, specs, axes, extra=extra)
    manifest = sc.build_manifest(run, seq, meta, records)
    uri = sc.write_manifest(backend, run, seq, manifest)
    return manifest, uri, records


# ---------------------------------------------------------------------------
# Shard math
# ---------------------------------------------------------------------------


def test_axis_split_bounds_balanced():
    assert sc.axis_split_bounds(8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]
    # Non-divisible: the first S % N shards carry one extra row and the
    # bounds tile the dimension exactly — the property resharding needs.
    bounds = sc.axis_split_bounds(13, 6)
    assert bounds[0] == (0, 3)
    assert bounds[-1] == (11, 13)
    assert [b - a for a, b in bounds] == [3, 2, 2, 2, 2, 2]
    # More shards than rows: trailing shards own empty ranges.
    assert sc.axis_split_bounds(2, 4)[-1] == (2, 2)
    with pytest.raises(ValueError):
        sc.axis_split_bounds(4, 0)


def test_shard_slices_and_overlap():
    axes = {"dp": 2, "fsdp": 2}
    # Dim 0 sharded over a tuple of axes composes row-major.
    spec = [["dp", "fsdp"], []]
    blocks = [sc.shard_slices((8, 3), spec, axes,
                              {"dp": d, "fsdp": f})
              for d in range(2) for f in range(2)]
    assert [b[0] for b in blocks] == [
        slice(0, 2), slice(2, 4), slice(4, 6), slice(6, 8)]
    assert all(b[1] == slice(0, 3) for b in blocks)
    assert sc.slices_overlap((slice(0, 4),), (slice(2, 6),)) == \
        (slice(2, 4),)
    assert sc.slices_overlap((slice(0, 2),), (slice(2, 6),)) is None
    # 0-d leaves: empty slice tuples overlap as () — NOT None.
    assert sc.slices_overlap((), ()) == ()


def test_normalize_spec_accepts_partition_spec():
    from jax.sharding import PartitionSpec
    assert sc.normalize_spec(PartitionSpec("fsdp", None), 2) == \
        [["fsdp"], []]
    assert sc.normalize_spec(PartitionSpec(("dp", "fsdp")), 2) == \
        [["dp", "fsdp"], []]
    assert sc.normalize_spec(None, 2) == [[], []]


# ---------------------------------------------------------------------------
# Manifest round-trip + restore/reshard (no cluster needed)
# ---------------------------------------------------------------------------


def test_manifest_roundtrip_and_full_restore(tmp_path):
    backend = spill.FileSpillBackend(str(tmp_path))
    state = _state_at(5)
    manifest, uri, records = _save_sharded(backend, "rt", 3, state, 8,
                                           extra={"step": 5})
    assert len(records) == 8
    ck = ShardedCheckpoint.from_manifest_uri(uri)
    assert ck.seq == 3 and ck.world_size == 8
    assert ck.extra == {"step": 5}
    assert ck.to_dict() == {"step": 5}
    restored = ck.load_full()
    assert _trees_equal(restored, state)
    # Container types survive the structure skeleton.
    assert isinstance(restored, dict) and isinstance(restored["opt"], list)
    # Monolithic payload APIs are refused, loudly.
    with pytest.raises(ValueError, match="load_for_rank"):
        ck.to_directory()


@pytest.mark.parametrize("new_world", [6, 4])
def test_reshard_numerical_identity(tmp_path, new_world):
    """A checkpoint saved on 8 ranks reassembles bit-identically on 6
    or 4 — per-rank blocks pulled as byte ranges from the old shards."""
    backend = spill.FileSpillBackend(str(tmp_path))
    state = _state_at(2)
    manifest, uri, _ = _save_sharded(backend, "rs", 1, state, 8)
    ck = ShardedCheckpoint.from_manifest_uri(uri)
    new_axes = [("fsdp", new_world)]
    reassembled = {p: np.empty(tuple(m["shape"]), np.dtype(m["dtype"]))
                   for p, m in manifest["params"].items()}
    for rank in range(new_world):
        local, _ = sc.flatten_tree(ck.load_for_rank(rank, new_world))
        coords = sc.rank_coords(rank, new_axes)
        for p, arr in local.items():
            slc = sc.shard_slices(tuple(manifest["params"][p]["shape"]),
                                  manifest["specs"][p], dict(new_axes),
                                  coords)
            reassembled[p][slc] = arr
    flat, structure = sc.flatten_tree(state)
    for p in flat:
        assert np.array_equal(np.asarray(flat[p]), reassembled[p]), p


def test_checksum_rejection(tmp_path):
    backend = spill.FileSpillBackend(str(tmp_path))
    manifest, uri, records = _save_sharded(backend, "crc", 1,
                                           _state_at(0), 2)
    # Corrupt one shard in place (same size, so only the crc catches it).
    victim = backend.path_for(backend.uri_for(records[1]["file"]))
    size = os.path.getsize(victim)
    with open(victim, "r+b") as f:
        f.seek(size // 2)
        f.write(b"\xff\xff\xff\xff")
    ck = ShardedCheckpoint.from_manifest_uri(uri)
    with pytest.raises(ValueError, match="checksum"):
        ck.load_full(verify=True)


# ---------------------------------------------------------------------------
# Two-phase commit: visibility, orphan GC, adoption, pruning
# ---------------------------------------------------------------------------


def test_uncommitted_shards_invisible_and_gcd(tmp_path):
    """Shard files without a manifest (rank died before the commit) are
    invisible to latest() and swept by the next index load."""
    mgr = CheckpointManager(str(tmp_path), "torn")
    backend = mgr._backend
    flat, structure = sc.flatten_tree(_state_at(1))
    specs = sc.default_specs(flat)
    for rank in range(2):  # both shards land, the manifest never does
        sc.write_shard(backend, "torn", 1, rank, flat, specs,
                       [("fsdp", 2)])
    assert mgr.latest() is None
    assert len(backend.list_files("train-torn-ckpt-")) == 2
    orphans_before = _counter_total(builtin_metrics.train_ckpt_orphans_gc())
    events.drain_pending()
    mgr2 = CheckpointManager(str(tmp_path), "torn")
    assert mgr2.latest() is None
    assert backend.list_files("train-torn-ckpt-") == []
    assert _counter_total(builtin_metrics.train_ckpt_orphans_gc()) >= \
        orphans_before + 2
    assert any("orphan" in e["message"] for e in events.drain_pending())


def test_corrupt_shard_uncommits_manifest_on_gc(tmp_path):
    """A committed manifest whose shard fails its checksum is
    uncommitted by GC: manifest + shards removed, latest() falls back."""
    mgr = CheckpointManager(str(tmp_path), "bitrot")
    backend = mgr._backend
    _save_sharded(backend, "bitrot", 1, _state_at(0), 2)  # good, older
    _, _, records = _save_sharded(backend, "bitrot", 2, _state_at(1), 2)
    victim = backend.path_for(backend.uri_for(records[0]["file"]))
    size = os.path.getsize(victim)
    with open(victim, "r+b") as f:
        f.seek(size // 2)
        f.write(b"\x00\x00\x00\x00")
    mgr2 = CheckpointManager(str(tmp_path), "bitrot")
    latest = mgr2.latest()
    assert isinstance(latest, ShardedCheckpoint)
    assert latest.seq == 1  # seq 2 was uncommitted by GC
    names = backend.list_files("train-bitrot-ckpt-")
    assert not any("000002" in n for n in names), names


def test_committed_manifest_adopted_into_index(tmp_path):
    """Crash AFTER the manifest write but BEFORE the index write: the
    checkpoint IS committed (manifest = commit record); the next index
    load adopts it."""
    mgr = CheckpointManager(str(tmp_path), "adopt")
    _save_sharded(mgr._backend, "adopt", 4, _state_at(3), 2,
                  extra={"step": 3})
    # mgr's in-memory index never saw it; a fresh load reconciles.
    mgr2 = CheckpointManager(str(tmp_path), "adopt")
    latest = mgr2.latest()
    assert isinstance(latest, ShardedCheckpoint)
    assert latest.seq == 4 and latest.extra == {"step": 3}
    assert mgr2.next_seq_base() == 5
    assert _trees_equal(latest.load_full(), _state_at(3))


def test_register_sharded_commits_and_prunes_all_files(tmp_path):
    """register_sharded writes the manifest last and num_to_keep
    pruning deletes manifest + every shard of evicted checkpoints —
    never the newest committed one."""
    mgr = CheckpointManager(str(tmp_path), "prune",
                            CheckpointConfig(num_to_keep=1))
    backend = mgr._backend
    for seq in (1, 2):
        state = _state_at(seq)
        flat, structure = sc.flatten_tree(state)
        specs = sc.default_specs(flat)
        records = [
            sc.write_shard(backend, "prune", seq, rank, flat, specs,
                           [("fsdp", 2)])
            for rank in range(2)
        ]
        meta = sc.build_tree_meta(flat, structure, specs,
                                  [("fsdp", 2)], extra={"step": seq})
        handle = mgr.register_sharded(seq, meta, records)
        assert isinstance(handle, ShardedCheckpoint)
    names = backend.list_files("train-prune-ckpt-")
    # Only seq 2 survives: 1 manifest + 2 shards.
    assert all("000002" in n for n in names), names
    assert len(names) == 3, names
    latest = mgr.latest()
    assert latest.seq == 2
    assert _trees_equal(latest.load_full(), _state_at(2))


def test_register_sharded_refuses_partial_gang(tmp_path):
    mgr = CheckpointManager(str(tmp_path), "partial")
    flat, structure = sc.flatten_tree(_state_at(0))
    specs = sc.default_specs(flat)
    rec = sc.write_shard(mgr._backend, "partial", 1, 1, flat, specs,
                         [("fsdp", 2)])
    meta = sc.build_tree_meta(flat, structure, specs, [("fsdp", 2)])
    with pytest.raises(ValueError, match="contiguous"):
        mgr.register_sharded(1, meta, [rec])  # rank 0 missing


def test_chaos_io_oserror_fails_write_keeps_prior(tmp_path):
    """An injected IO error on a shard write surfaces as SpillFailure
    (the save attempt fails cleanly); the previously committed
    checkpoint is untouched and restorable."""
    backend = spill.FileSpillBackend(str(tmp_path))
    manifest, uri, _ = _save_sharded(backend, "io", 1, _state_at(7), 2,
                                     extra={"step": 7})
    flat, _ = sc.flatten_tree(_state_at(8))
    specs = sc.default_specs(flat)
    chaos.configure(
        "io_oserror:site=train.ckpt_shard_write_error:times=1")
    try:
        with pytest.raises(spill.SpillFailure):
            sc.write_shard(backend, "io", 2, 0, flat, specs,
                           [("fsdp", 2)])
    finally:
        chaos.reset()
    prior = ShardedCheckpoint.from_manifest_uri(uri)
    assert _trees_equal(prior.load_full(), _state_at(7))


def test_mock_s3_backend_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_MOCK_S3_DIR", str(tmp_path / "s3"))
    mgr = CheckpointManager("mock-s3://ckpt-bucket", "cloudy")
    flat, structure = sc.flatten_tree(_state_at(1))
    specs = sc.default_specs(flat)
    records = [
        sc.write_shard(mgr._backend, "cloudy", 1, rank, flat, specs,
                       [("fsdp", 2)])
        for rank in range(2)
    ]
    meta = sc.build_tree_meta(flat, structure, specs, [("fsdp", 2)],
                              extra={"step": 1})
    handle = mgr.register_sharded(1, meta, records)
    assert handle.uri.startswith("mock-s3://ckpt-bucket/")
    # A brand-new manager (fresh process analog) restores through the
    # same bucket URI.
    latest = CheckpointManager("mock-s3://ckpt-bucket", "cloudy").latest()
    assert isinstance(latest, ShardedCheckpoint)
    assert _trees_equal(latest.load_full(), _state_at(1))


# ---------------------------------------------------------------------------
# The streamed shard: byte-identical to the parent's writer
# ---------------------------------------------------------------------------


def _tobytes_shard(flat, specs, axes_items, rank):
    """The writer as it was before the shard was streamed, kept as the
    oracle: slice, ``ascontiguousarray``, ``tobytes``, and two CRC passes
    over every byte. Returns the file's bytes and the record's fields."""
    axes = dict(axes_items)
    coords = sc.rank_coords(rank, axes_items)
    blocks, parts, offset, file_crc = {}, [], 0, 0
    for path in sorted(flat):
        a = np.asarray(flat[path])
        spec = sc.normalize_spec(specs.get(path), a.ndim)
        block = a[sc.shard_slices(a.shape, spec, axes, coords)]
        block = np.ascontiguousarray(block).reshape(np.shape(block))
        raw = block.tobytes()
        blocks[path] = {"offset": offset, "length": len(raw),
                        "crc32": zlib.crc32(raw) & 0xFFFFFFFF,
                        "shape": [int(n) for n in block.shape],
                        "dtype": str(block.dtype)}
        file_crc = zlib.crc32(raw, file_crc)
        parts.append(raw)
        offset += len(raw)
    return b"".join(parts), {"bytes": offset, "blocks": blocks,
                             "crc32": file_crc & 0xFFFFFFFF}


def _leaf(kind, seed=0):
    import ml_dtypes
    rng = np.random.default_rng(seed)
    if kind == "bf16":
        return rng.standard_normal((6, 5)).astype(ml_dtypes.bfloat16)
    if kind == "scalar":
        return np.float32(rng.standard_normal())
    if kind == "empty":
        return np.empty((0, 3), np.float32)
    if kind == "fortran":  # whole, in the transposed memory order
        return np.asfortranarray(
            rng.standard_normal((7, 300)).astype(ml_dtypes.bfloat16))
    if kind == "fortran3d":
        return np.asfortranarray(rng.integers(0, 99, (3, 4, 5)))
    assert kind == "dim1"  # a rank's block is a strided slice
    return rng.standard_normal((4, 9)).astype(np.float32)


LEAF_KINDS = ["bf16", "scalar", "empty", "fortran", "fortran3d", "dim1"]


def _tree_of(kinds):
    flat = {f"{i}-{kind}": _leaf(kind, seed=i)
            for i, kind in enumerate(kinds)}
    specs = sc.default_specs(flat)
    for path in flat:
        if path.endswith("dim1"):
            specs[path] = [[], ["fsdp"]]
    return flat, specs


@pytest.mark.parametrize("world,rank", [(1, 0), (2, 0), (2, 1)])
@pytest.mark.parametrize("kinds", [[k] for k in LEAF_KINDS] + [LEAF_KINDS],
                         ids=LEAF_KINDS + ["all"])
def test_streamed_shard_is_byte_identical(tmp_path, kinds, world, rank):
    """The shard file and every field the manifest takes from its record
    equal what the ``tobytes`` writer produced for the same state."""
    flat, specs = _tree_of(kinds)
    axes = [("fsdp", world)]
    backend = spill.FileSpillBackend(str(tmp_path))
    record = sc.write_shard(backend, "same", 3, rank, flat, specs, axes)
    want_bytes, want = _tobytes_shard(flat, specs, axes, rank)
    with open(backend.path_for(record["uri"]), "rb") as f:
        assert f.read() == want_bytes
    assert {k: record[k] for k in want} == want
    assert record["file"] == sc.shard_filename("same", 3, rank)
    assert record["write_s"] >= 0.0
    assert os.listdir(tmp_path) == [record["file"]]  # no .tmp left
    for path, block in sc.extract_local_shard(flat, specs, axes,
                                              rank).items():
        assert block.flags.c_contiguous
        assert block.tobytes() == want_bytes[
            want["blocks"][path]["offset"]:][:want["blocks"][path]["length"]]


@pytest.mark.parametrize("world", [1, 2])
def test_streamed_checkpoint_restores_and_reshards(tmp_path, world):
    flat, specs = _tree_of(LEAF_KINDS)
    axes = [("fsdp", world)]
    backend = spill.FileSpillBackend(str(tmp_path))
    _, structure = sc.flatten_tree(flat)
    records = [sc.write_shard(backend, "rt", 1, rank, flat, specs, axes)
               for rank in range(world)]
    meta = sc.build_tree_meta(flat, structure, specs, axes)
    uri = sc.write_manifest(backend, "rt", 1,
                            sc.build_manifest("rt", 1, meta, records))
    ck = ShardedCheckpoint.from_manifest_uri(uri)
    assert sc.validate_shards(backend, ck.manifest, verify_checksums=True)
    assert _trees_equal(ck.load_full(verify=True), flat)
    for rank in range(3):  # reshard onto three ranks
        got = ck.load_for_rank(rank, world_size=3, verify=True)
        want = sc.extract_local_shard(flat, specs, [("fsdp", 3)], rank)
        assert _trees_equal(got, want)


def test_checkpoint_of_the_tobytes_writer_restores(tmp_path):
    """A checkpoint written before the shard was streamed restores."""
    flat, specs = _tree_of(LEAF_KINDS)
    axes = [("fsdp", 2)]
    backend = spill.FileSpillBackend(str(tmp_path))
    _, structure = sc.flatten_tree(flat)
    records = []
    for rank in range(2):
        data, fields = _tobytes_shard(flat, specs, axes, rank)
        name = sc.shard_filename("old", 1, rank)
        backend.write(name, data)
        records.append(dict(fields, rank=rank, file=name))
    meta = sc.build_tree_meta(flat, structure, specs, axes)
    uri = sc.write_manifest(backend, "old", 1,
                            sc.build_manifest("old", 1, meta, records))
    ck = ShardedCheckpoint.from_manifest_uri(uri)
    assert _trees_equal(ck.load_full(verify=True), flat)


@pytest.mark.parametrize("seed", range(4))
def test_crc32_combine_equals_zlib_on_random_splits(seed):
    rng = random.Random(seed)
    for _ in range(25):
        parts = [rng.randbytes(rng.choice([0, 0, 1, 2, 31, 1024, 70001]))
                 for _ in range(rng.randint(1, 5))]
        crc = 0
        for part in parts:
            crc = sc.crc32_combine(crc, zlib.crc32(part), len(part))
        assert crc == zlib.crc32(b"".join(parts))
    # Bits above 32 in an input are not the caller's to clear.
    assert sc.crc32_combine(zlib.crc32(b"ab"), zlib.crc32(b"c"), 1) == \
        zlib.crc32(b"abc")


def test_device_leaves_are_prefetched_and_host_leaves_skipped(tmp_path):
    """Every ``jax.Array`` leaf's transfer is started before the first
    leaf is read; numpy and scalar leaves have no transfer to start."""
    import jax.numpy as jnp
    started, read = [], []

    class Leaf:
        def __init__(self, name, value):
            self.name, self.value = name, value

        def copy_to_host_async(self):
            assert not read, "a leaf was read before every transfer began"
            started.append(self.name)

        def __array__(self, dtype=None, copy=None):
            read.append(self.name)
            return self.value

    flat = {"b": Leaf("b", np.arange(6.0)), "a": Leaf("a", np.ones(3)),
            "host": np.arange(4), "scalar": 2.5,
            "device": jnp.arange(8, dtype=jnp.bfloat16)}
    backend = spill.FileSpillBackend(str(tmp_path))
    record = sc.write_shard(backend, "pre", 1, 0, flat,
                            {p: [] for p in flat}, [("fsdp", 1)])
    assert started == ["a", "b"] and read == ["a", "b"]  # sorted paths
    want_bytes, want = _tobytes_shard(flat, {}, [("fsdp", 1)], 0)
    assert record["crc32"] == want["crc32"]
    assert backend.read(record["uri"]) == want_bytes


# ---------------------------------------------------------------------------
# Failure paths under streaming
# ---------------------------------------------------------------------------


def _write_failures():
    return _counter_total(builtin_metrics.object_spill_failures(), "write")


def _shard_session(tmp_path, run="fail"):
    """A rank-0 session as the BackendExecutor hands it to a worker, with
    the driver's side of its queue played as ``get_next_result`` plays it:
    every item is taken (into ``s.taken``) and a report's sender let go."""
    s = session._Session(ckpt_ctx={
        "run": run, "storage_uri": "file://" + str(tmp_path),
        "seq_base": 1})
    s.taken = queue.Queue()

    def take():
        while True:
            item = s.result_queue.get()
            s.taken.put(item)
            if "ack" not in item:
                s.continue_event.set()

    threading.Thread(target=take, daemon=True).start()
    return s


def _report_then_ack(s):
    """The two items of one save, once its writer is done."""
    s.wait_for_writer()
    items = [s.taken.get(timeout=10), s.taken.get(timeout=10)]
    [report] = [i for i in items if "ack" not in i]
    [ack] = [i for i in items if "ack" in i]
    assert set(report) == {"metrics", "checkpoint"}
    assert ack["metrics"] == report["metrics"]
    return report, ack["ack"]


@pytest.mark.parametrize("fault", ["chaos_after_2_leaves", "chaos_at_open",
                                   "fsync_oserror", "write_oserror",
                                   "sync_oserror"])
def test_failed_stream_leaves_nothing_and_reports_error(tmp_path,
                                                        monkeypatch, fault):
    """An ``OSError``, real or injected at ``spill.write_error``, at the
    open, after k leaves, at a sync or at the fsync: no ``.tmp``, no
    shard, one write failure counted, and the rank's writer acks
    ``{"error": ...}``."""
    state = {f"w{i}": np.full((8, 4), float(i), np.float32)
             for i in range(5)}
    if fault in ("fsync_oserror", "sync_oserror"):
        def refuse(fd):
            raise OSError(28, "No space left on device")
        if fault == "sync_oserror":  # the wait for the disk, mid-file
            monkeypatch.setattr(sc, "_SYNC_BYTES", 2 * 8 * 4 * 4)
        monkeypatch.setattr(
            os, "fsync" if fault == "fsync_oserror" else "fdatasync", refuse)
    elif fault == "write_oserror":
        real_open = open

        class Full:
            def __init__(self, f):
                self.f, self.calls = f, 0

            def write(self, part):
                self.calls += 1
                if self.calls == 3:
                    raise OSError(28, "No space left on device")
                return self.f.write(part)

            def __getattr__(self, name):
                return getattr(self.f, name)

        monkeypatch.setattr(
            spill, "open", lambda *a, **k: Full(real_open(*a, **k)),
            raising=False)
    else:
        # The site is evaluated at the open, then once a part.
        after = 3 if fault == "chaos_after_2_leaves" else 0
        chaos.configure(
            f"io_oserror:site=spill.write_error:after={after}:times=1")
    before = _write_failures()
    s = _shard_session(tmp_path)
    try:
        s.report_sharded({"step": 1}, state)
        report, shard = _report_then_ack(s)
    finally:
        chaos.reset()
        monkeypatch.undo()
    assert report["metrics"] == {"step": 1}
    assert shard["seq"] == 1 and shard["rank"] == 0
    assert "spill write of train-fail-ckpt-000001.shard-0000 failed" in \
        shard["error"]
    assert "blocks" not in shard and "tree_meta" not in shard
    assert os.listdir(tmp_path) == []
    assert _write_failures() == before + 1
    # The next save of the same session goes through.
    s.report_sharded({"step": 2}, state)
    _, shard = _report_then_ack(s)
    assert "error" not in shard and shard["seq"] == 2
    assert os.listdir(tmp_path) == [shard["file"]]


def test_a_failure_that_is_no_oserror_aborts_the_stream(tmp_path):
    """A leaf that cannot be read (a donated device array, say) is the
    caller's error, not a spill failure: it propagates, and the writer
    still leaves no ``.tmp`` behind."""
    class Gone:
        shape, ndim, dtype = (2,), 1, np.dtype(np.float32)

        def __array__(self, dtype=None, copy=None):
            raise RuntimeError("Array has been deleted")

    flat = {"a": np.ones(4, np.float32), "b": Gone()}
    backend = spill.FileSpillBackend(str(tmp_path))
    before = _write_failures()
    with pytest.raises(RuntimeError, match="deleted"):
        sc.write_shard(backend, "gone", 1, 0, flat, {}, [("fsdp", 1)])
    assert os.listdir(tmp_path) == []
    assert _write_failures() == before


def test_chaos_kill_leaves_the_shard_unwritten(tmp_path):
    """``train.ckpt_shard_kill`` fires before the first byte: no shard, no
    ``.tmp``, the rank is told to play dead, and the previous manifest
    stays the newest."""
    mgr = CheckpointManager(str(tmp_path), "fail")
    _save_sharded(mgr._backend, "fail", 1, _state_at(1), 1,
                  extra={"step": 1})
    names = sorted(os.listdir(tmp_path))
    s = _shard_session(tmp_path)
    s._shard_reports = 1  # this save is seq 2
    played_dead = []
    s.on_chaos_kill = lambda: played_dead.append(True)
    chaos.configure("kill:site=train.ckpt_shard_kill:times=1")
    try:
        with pytest.raises(chaos.ChaosKill):
            s.report_sharded({"step": 2}, _state_at(2))
    finally:
        chaos.reset()
    assert played_dead == [True] and s.taken.empty()
    assert sorted(os.listdir(tmp_path)) == names
    latest = CheckpointManager(str(tmp_path), "fail").latest()
    assert latest.seq == 1 and latest.extra == {"step": 1}


def test_a_reader_never_sees_a_partial_shard(tmp_path, monkeypatch):
    """While the leaves are written, the bytes are under ``.tmp`` only: the
    final name appears with the rename, after the fsync. Nothing is there
    at all while the leaves are gathered."""
    backend = spill.FileSpillBackend(str(tmp_path))
    name = sc.shard_filename("part", 1, 0)
    seen = []

    def look():
        seen.append((backend.list_files(), sorted(os.listdir(tmp_path)),
                     backend.size_of(backend.uri_for(name))))

    class Watching:
        """A leaf that looks at the storage when its turn comes."""

        def __init__(self, value):
            self.value = value

        def __array__(self, dtype=None, copy=None):
            look()
            return self.value

    write = spill.SpillWriter.write

    def watched_write(self, part):
        look()
        write(self, part)

    monkeypatch.setattr(spill.SpillWriter, "write", watched_write)
    flat = {f"w{i}": Watching(np.full((16,), float(i))) for i in range(3)}
    record = sc.write_shard(backend, "part", 1, 0, flat, {}, [("fsdp", 1)])
    assert seen == [([], [], None)] * 3 + [([], [name + ".tmp"], None)] * 3
    assert backend.list_files() == [name]
    assert backend.size_of(record["uri"]) == record["bytes"] == 3 * 16 * 8


def test_the_writer_waits_for_the_disk_as_it_goes(tmp_path, monkeypatch):
    """Every ``_SYNC_BYTES`` written the writer waits until they are on
    the disk, so the unflushed part of a shard stays bounded; the file is
    the same bytes, and the waits are ``ckpt::write`` spans of their own."""
    state = {f"w{i}": np.full((8, 4), float(i), np.float32)
             for i in range(7)}  # 128 bytes a leaf
    flat, _ = sc.flatten_tree(state)
    backend = spill.FileSpillBackend(str(tmp_path))
    name = sc.shard_filename("sync", 1, 0)
    on_disk = []
    fdatasync = os.fdatasync

    def watched(fd):
        fdatasync(fd)
        on_disk.append(os.stat(str(tmp_path / (name + ".tmp"))).st_size)

    monkeypatch.setattr(os, "fdatasync", watched)
    monkeypatch.setattr(sc, "_SYNC_BYTES", 300)
    tracing.clear_spans()
    tracing.enable_tracing()
    try:
        with tracing.start_span("train::report_sharded"):
            record = sc.write_shard(backend, "sync", 1, 0, flat, {},
                                    [("fsdp", 1)])
    finally:
        tracing.disable_tracing()
    writes = [s.attributes for s in tracing.get_spans()
              if s.name == "ckpt::write"]
    tracing.clear_spans()
    assert on_disk == [384, 768]  # after the third leaf and the sixth
    assert [w for w in writes if "what" in w] == [
        {"what": "sync", "bytes": 384}, {"what": "sync", "bytes": 384},
        {"what": "commit", "bytes": 896, "seq": 1, "rank": 0}]
    want_bytes, want = _tobytes_shard(flat, {}, [("fsdp", 1)], 0)
    assert backend.read(record["uri"]) == want_bytes
    assert record["crc32"] == want["crc32"]


def test_config_knobs_present():
    from ray_tpu._private.ray_config import _PY_DEFAULTS
    assert _PY_DEFAULTS["train_ckpt_shard_parallelism"] == 8
    assert _PY_DEFAULTS["train_ckpt_verify_checksums"] is True
    assert _PY_DEFAULTS["train_reshard_on_restart"] is True


def test_shard_parallelism_one_still_loads(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_train_ckpt_shard_parallelism", "1")
    backend = spill.FileSpillBackend(str(tmp_path))
    _, uri, _ = _save_sharded(backend, "serial", 1, _state_at(4), 4)
    ck = ShardedCheckpoint.from_manifest_uri(uri)
    assert _trees_equal(ck.load_full(), _state_at(4))


# ---------------------------------------------------------------------------
# End-to-end through the gang (report_sharded -> two-phase commit)
# ---------------------------------------------------------------------------


def _sharded_loop(total):
    def loop():
        rank = session.get_world_rank()
        world = session.get_world_size()
        ckpt = session.get_checkpoint()
        start = 0
        resume_ok = 1.0
        if ckpt is not None:
            start = ckpt.to_dict()["step"]
            # The restore path every rank takes on (re)start: my block
            # under the CURRENT mesh, resharded from the saved one.
            local, _ = sc.flatten_tree(ckpt.load_for_rank(rank, world))
            flat, _ = sc.flatten_tree(_state_at(start))
            specs = sc.default_specs(flat)
            expected = sc.extract_local_shard(flat, specs,
                                              [("fsdp", world)], rank)
            for p, arr in expected.items():
                if not np.array_equal(arr, np.asarray(local[p])):
                    resume_ok = 0.0
        for i in range(start, total):
            session.report_sharded(
                {"step": i, "world": world, "resume_ok": resume_ok},
                _state_at(i + 1), extra={"step": i + 1})
    return loop


def test_sharded_train_end_to_end(ray_start_regular, tmp_path):
    """4 ranks each write their own shard file every save; the driver
    commits the manifest after all acks; metrics/journal record it."""
    persisted_before = _counter_total(
        builtin_metrics.train_checkpoints_persisted())
    saves_hist = builtin_metrics.train_ckpt_save_seconds()
    saves_before = sum(saves_hist._counts.values())
    events.drain_pending()

    trainer = DataParallelTrainer(
        _sharded_loop(3),
        scaling_config=ScalingConfig(num_workers=4),
        run_config=RunConfig(name="shard-e2e",
                             storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.metrics["step"] == 2
    ck = result.checkpoint
    assert isinstance(ck, ShardedCheckpoint)
    assert ck.world_size == 4 and ck.extra == {"step": 3}
    assert _trees_equal(ck.load_full(), _state_at(3))

    # N parallel per-rank shard files on storage, per-rank byte counters.
    names = [n for n in os.listdir(tmp_path) if ".shard-" in n]
    assert {n.rsplit("-", 1)[1] for n in names} >= \
        {"0000", "0001", "0002", "0003"}
    shard_bytes = builtin_metrics.train_ckpt_shard_bytes().series()
    ranks_seen = {part for key in shard_bytes for part in key}
    assert {"0", "1", "2", "3"} <= ranks_seen
    assert _counter_total(
        builtin_metrics.train_checkpoints_persisted()) >= \
        persisted_before + 3
    assert sum(saves_hist._counts.values()) >= saves_before + 3
    msgs = [e["message"] for e in events.drain_pending()]
    assert any("sharded checkpoint" in m and "committed" in m
               for m in msgs), msgs

    # A fresh run under the same name auto-resumes from the commit.
    second = DataParallelTrainer(
        _sharded_loop(5),
        scaling_config=ScalingConfig(num_workers=4),
        run_config=RunConfig(name="shard-e2e",
                             storage_path=str(tmp_path)))
    r2 = second.fit()
    assert r2.metrics["step"] == 4
    assert r2.metrics["resume_ok"] == 1.0
    assert len(r2.metrics_history) == 2  # started at step 3
    assert r2.checkpoint.extra == {"step": 5}


def test_chaos_shard_write_error_save_aborts_cleanly(ray_start_regular,
                                                    tmp_path):
    """One rank's shard write raises: that save attempt aborts without
    a manifest, training continues, later saves commit normally."""
    failures_before = _counter_total(
        builtin_metrics.train_checkpoint_persist_failures())
    trainer = DataParallelTrainer(
        _sharded_loop(3),
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="shard-io", storage_path=str(tmp_path)))
    chaos.configure(
        "io_oserror:site=train.ckpt_shard_write_error:times=1")
    try:
        result = trainer.fit()
        fired = any(op["fired"] for op in chaos.stats())
    finally:
        chaos.reset()
    assert fired, "chaos io error never fired"
    assert result.metrics["step"] == 2
    # The first save (step 1) aborted; the run's last save committed.
    assert result.checkpoint.extra == {"step": 3}
    assert _trees_equal(result.checkpoint.load_full(), _state_at(3))
    assert _counter_total(
        builtin_metrics.train_checkpoint_persist_failures()) >= \
        failures_before + 1
    # No torn seq-1 manifest on storage.
    manifests = [n for n in os.listdir(tmp_path) if n.endswith(".manifest")]
    assert not any("000001" in n for n in manifests), manifests


def test_chaos_sigkill_rank_mid_save_acceptance(ray_start_regular,
                                                tmp_path):
    """ISSUE 20 chaos acceptance: SIGKILL one rank mid-save -> the
    partial save never commits, the gang restarts, resume loads the
    last COMMITTED checkpoint, and the next index load GCs the torn
    shard set."""
    restarts_before = _counter_total(
        builtin_metrics.train_gang_restarts(), "system")
    trainer = DataParallelTrainer(
        _sharded_loop(4),
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            name="shard-kill", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=1)))
    # after=2: save 1's two shard writes pass, then the first rank to
    # reach save 2's kill gate dies with its shard unwritten — the
    # other rank's seq-2 shard becomes commit-less debris.
    chaos.configure("kill:site=train.ckpt_shard_kill:after=2:times=1")
    try:
        result = trainer.fit()
        fired = any(op["fired"] for op in chaos.stats())
    finally:
        chaos.reset()
    assert fired, "chaos kill never fired"
    # The run finished its full target on the restarted gang.
    assert result.metrics["step"] == 3
    assert result.metrics["resume_ok"] == 1.0
    assert result.checkpoint.extra == {"step": 4}
    assert _trees_equal(result.checkpoint.load_full(), _state_at(4))
    assert _counter_total(builtin_metrics.train_gang_restarts(),
                          "system") >= restarts_before + 1
    events.drain_pending()
    # The torn shard set is debris until the next index load sweeps it.
    mgr = CheckpointManager(str(tmp_path), "shard-kill")
    latest = mgr.latest()
    assert isinstance(latest, ShardedCheckpoint)
    assert latest.extra == {"step": 4}
    committed = {f for e in mgr._tracked for f in e.get("files", [])} | \
        {os.path.basename(e["uri"].split("://", 1)[1])
         for e in mgr._tracked}
    leftover = [n for n in mgr._backend.list_files("train-shard-kill-ckpt-")
                if ".shard-" in n or n.endswith(".manifest")]
    assert all(n in committed for n in leftover), (leftover, committed)


def test_elastic_shrink_reshard_acceptance(ray_start_regular, monkeypatch,
                                           tmp_path):
    """ISSUE 20 elastic acceptance: mid-run shrink 8 -> min_workers 4
    resumes via reshard with numerically identical params and finishes
    the full target step count; reshards_total{shrink} increments."""
    shrink_before = _counter_total(builtin_metrics.train_reshards(),
                                   "shrink")
    _set_flag("train_restart_wait_s", 0.1)
    monkeypatch.setattr(BackendExecutor, "_placeable_workers",
                        lambda self, desired: 4)

    def loop():
        rank = session.get_world_rank()
        world = session.get_world_size()
        ckpt = session.get_checkpoint()
        start = 0
        resume_ok = 1.0
        if ckpt is not None:
            start = ckpt.to_dict()["step"]
            local, _ = sc.flatten_tree(ckpt.load_for_rank(rank, world))
            flat, _ = sc.flatten_tree(_state_at(start))
            specs = sc.default_specs(flat)
            expected = sc.extract_local_shard(flat, specs,
                                              [("fsdp", world)], rank)
            for p, arr in expected.items():
                if not np.array_equal(arr, np.asarray(local[p])):
                    resume_ok = 0.0
        for i in range(start, 4):
            session.report_sharded(
                {"step": i, "world": world, "resume_ok": resume_ok},
                _state_at(i + 1), extra={"step": i + 1})
            if world == 8 and i + 1 >= 2:
                raise RuntimeError("slice lost")

    mgr = CheckpointManager(str(tmp_path), "elastic-shrink")
    executor = BackendExecutor(
        BackendConfig(),
        ScalingConfig(num_workers=8, min_workers=4),
        FailureConfig(max_failures=1),
        checkpoint_manager=mgr)
    executor.start()
    try:
        result = executor.run(loop, {}, {"trial_id": "shrink"})
    finally:
        executor.shutdown()
    # Finished the FULL target on the 4-rank gang.
    assert result.metrics["step"] == 3
    assert result.metrics["world"] == 4
    assert result.metrics["resume_ok"] == 1.0
    ck = result.checkpoint
    assert isinstance(ck, ShardedCheckpoint)
    assert ck.world_size == 4 and ck.extra == {"step": 4}
    assert _trees_equal(ck.load_full(), _state_at(4))
    assert _counter_total(builtin_metrics.train_reshards(), "shrink") >= \
        shrink_before + 1


# ---------------------------------------------------------------------------
# The save behind the loop: report_sharded returns when the state is on the
# host; checksum, write, fsync and ack follow on the rank's writer thread
# ---------------------------------------------------------------------------


def _hold_writer(monkeypatch, held=lambda seq, rank: True):
    """Every save's second half for which ``held(seq, rank)`` waits, on
    its writer thread, for the event this returns."""
    gate = threading.Event()
    write = sc.write_gathered

    def gated(backend, run, seq, rank, blocks):
        if held(seq, rank):
            assert gate.wait(30), "nobody let the writer go"
        return write(backend, run, seq, rank, blocks)

    monkeypatch.setattr(sc, "write_gathered", gated)
    return gate


def _saves_observed():
    return sum(builtin_metrics.train_ckpt_save_seconds()._counts.values())


def _wait_until(what, seconds=20.0):
    deadline = time.monotonic() + seconds
    while not what():
        assert time.monotonic() < deadline, "waited in vain"
        time.sleep(0.005)


def test_report_sharded_returns_before_the_shard_exists(tmp_path,
                                                        monkeypatch):
    gate = _hold_writer(monkeypatch)
    s = _shard_session(tmp_path)
    s.report_sharded({"step": 1}, _state_at(1))
    # Back in the loop: the report is with the driver, nothing is on
    # storage and nothing is acked.
    assert s.taken.get(timeout=10) == {"metrics": {"step": 1},
                                       "checkpoint": None}
    assert os.listdir(tmp_path) == [] and s.taken.empty()
    gate.set()
    s.wait_for_writer()
    ack = s.taken.get(timeout=10)
    assert ack["metrics"] == {"step": 1} and "checkpoint" not in ack
    assert os.listdir(tmp_path) == [ack["ack"]["file"]]
    assert ack["ack"]["tree_meta"]["extra"] == {}
    s.wait_for_writer()  # nothing in flight: returns at once


@pytest.mark.parametrize("after", ["overwritten", "deleted"])
def test_state_changed_after_the_return_restores_the_saved_bytes(
        tmp_path, monkeypatch, after):
    """From the return on the state is the caller's again: what reaches
    the file is what it held at the call, host leaves and device leaves
    alike."""
    import jax.numpy as jnp
    gate = _hold_writer(monkeypatch)
    want = _state_at(3)
    state = _state_at(3)
    state["dev"] = jnp.arange(24, dtype=jnp.bfloat16).reshape(6, 4)
    want["dev"] = np.array(state["dev"])
    s = _shard_session(tmp_path, run="mine")
    s.report_sharded({"step": 3}, state, extra={"step": 3})
    if after == "overwritten":
        state["w"][...] = -1.0
        state["b"] *= 0
        state["opt"][0][...] = 7.0
    else:
        state["dev"].delete()
        del state
    gate.set()
    _, shard = _report_then_ack(s)
    manifest = sc.build_manifest("mine", 1, shard["tree_meta"], [shard])
    uri = sc.write_manifest(spill.FileSpillBackend(str(tmp_path)), "mine",
                            1, manifest)
    restored = ShardedCheckpoint.from_manifest_uri(uri)
    assert restored.extra == {"step": 3}
    assert _trees_equal(restored.load_full(), want)


def test_a_second_save_waits_for_the_first_and_records_the_wait(
        tmp_path, monkeypatch):
    """One save in flight a rank: the next waits, inside its own stall
    and under a span of its own, until the first is written and acked."""
    gate = _hold_writer(monkeypatch, held=lambda seq, rank: seq == 1)
    s = _shard_session(tmp_path)
    tracing.clear_spans()
    tracing.enable_tracing()
    try:
        s.report_sharded({"step": 1}, _state_at(1))
        second = threading.Thread(
            target=s.report_sharded, args=({"step": 2}, _state_at(2)))
        t0 = time.perf_counter()
        second.start()
        second.join(0.2)
        assert second.is_alive() and os.listdir(tmp_path) == []
        held_s = time.perf_counter() - t0
        gate.set()
        second.join(30)
        assert not second.is_alive()
        s.wait_for_writer()
    finally:
        tracing.disable_tracing()
    spans = tracing.get_spans()
    tracing.clear_spans()
    acks = []
    while len(acks) < 2:
        item = s.taken.get(timeout=10)
        acks += [item["ack"]] if "ack" in item else []
    assert [a["seq"] for a in acks] == [1, 2]
    assert not any("error" in a for a in acks)
    saves = {sp.attributes["seq"]: sp for sp in spans
             if sp.name == "train::report_sharded"}
    waits = {seq: [sp for sp in spans if sp.name == "ckpt::drain_wait"
                   and sp.parent_id == save.span_id]
             for seq, save in saves.items()}
    assert [len(waits[1]), len(waits[2])] == [1, 1]
    assert waits[1][0].duration < 0.05
    assert held_s * 0.9 <= waits[2][0].duration <= saves[2].duration
    # The first save's file was whole before the second's first byte.
    first_done = max(sp.perf_start + sp.duration for sp in spans
                     if sp.name == "ckpt::write"
                     and sp.parent_id == saves[1].span_id)
    assert first_done <= min(sp.perf_start for sp in spans
                             if sp.name == "ckpt::gather"
                             and sp.parent_id == saves[2].span_id)


def test_the_commit_arrives_with_no_further_report(ray_start_regular,
                                                   tmp_path):
    """The ack does not ride the loop's next report: the driver commits
    while the train function sits between two reports."""
    seen = {}

    def loop():
        before = _saves_observed()
        session.report_sharded({"step": 0}, _state_at(1),
                               extra={"step": 1})
        _wait_until(lambda: _saves_observed() > before)
        seen["manifests"] = [n for n in os.listdir(tmp_path)
                             if n.endswith(".manifest")]
        session.report({"step": 1})

    result = DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="no-report",
                             storage_path=str(tmp_path))).fit()
    assert seen["manifests"] == [sc.manifest_filename("no-report", 1)]
    assert [m["step"] for m in result.metrics_history] == [0, 1]
    assert result.checkpoint.extra == {"step": 1}


def test_a_function_that_returns_with_a_save_in_flight_yields_it(
        ray_start_regular, tmp_path, monkeypatch):
    """``fit()`` waits for the last save: the writer is held until the
    train function has returned and the rank waits for it, and the
    ``Result`` names that save, committed."""
    gate = _hold_writer(monkeypatch)
    order = []
    wait = session._Session.wait_for_writer

    def waited(self):
        if order:  # not the save's own wait, for a save before it
            order.append(("waits", sorted(os.listdir(tmp_path))))
            gate.set()
        wait(self)

    monkeypatch.setattr(session._Session, "wait_for_writer", waited)

    def loop():
        session.report_sharded({"step": 0}, _state_at(1),
                               extra={"step": 1})
        order.append(("returns", sorted(os.listdir(tmp_path))))

    result = DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="last",
                             storage_path=str(tmp_path))).fit()
    assert order == [("returns", []), ("waits", [])]
    assert isinstance(result.checkpoint, ShardedCheckpoint)
    assert result.checkpoint.extra == {"step": 1}
    assert _trees_equal(result.checkpoint.load_full(), _state_at(1))
    assert len(result.metrics_history) == 1


def test_a_write_error_behind_the_loop_fails_that_save_alone(
        ray_start_regular, tmp_path):
    """``spill.write_error`` fires on the writer thread, mid-file: an
    ``{"error": ...}`` ack, one persist failure, no seq-2 manifest, the
    first checkpoint still the newest, and the next save clean."""
    failures = builtin_metrics.train_checkpoint_persist_failures()
    seen = {}

    def loop():
        saves, failed = _saves_observed(), _counter_total(failures)
        session.report_sharded({"step": 0}, _state_at(1),
                               extra={"step": 1})
        _wait_until(lambda: _saves_observed() > saves)
        # The site is evaluated at the open, then once a part.
        chaos.configure(
            "io_oserror:site=spill.write_error:after=2:times=1")
        session.report_sharded({"step": 1}, _state_at(2),
                               extra={"step": 2})
        _wait_until(lambda: _counter_total(failures) > failed)
        seen["fired"] = any(op["fired"] for op in chaos.stats())
        chaos.reset()
        seen["failed"] = _counter_total(failures) - failed
        seen["newest"] = CheckpointManager(
            str(tmp_path), "behind").latest().extra
        seen["files"] = sorted(os.listdir(tmp_path))
        session.report_sharded({"step": 2}, _state_at(3),
                               extra={"step": 3})

    try:
        result = DataParallelTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="behind",
                                 storage_path=str(tmp_path))).fit()
    finally:
        chaos.reset()
    assert seen["fired"] and seen["failed"] == 1
    assert seen["newest"] == {"step": 1}
    assert not any("000002" in n or n.endswith(".tmp")
                   for n in seen["files"]), seen["files"]
    assert [m["step"] for m in result.metrics_history] == [0, 1, 2]
    assert result.checkpoint.extra == {"step": 3}
    assert _trees_equal(result.checkpoint.load_full(), _state_at(3))


def test_acks_of_one_seq_in_different_rounds_commit_once(
        ray_start_regular, tmp_path, monkeypatch):
    """World 2, rank 1's writer held: rank 0's ack is taken rounds before
    rank 1's, both ranks report on meanwhile, and the save commits once,
    when the second ack is in, with the metrics of the report that began
    it."""
    gate = _hold_writer(monkeypatch,
                        held=lambda seq, rank: (seq, rank) == (1, 1))
    commits = []
    commit = BackendExecutor._commit_sharded

    def counted(self, shard_acks, world, metrics):
        handle = commit(self, shard_acks, world, metrics)
        commits.append((sorted(shard_acks), metrics, handle,
                        gate.is_set()))
        return handle

    monkeypatch.setattr(BackendExecutor, "_commit_sharded", counted)

    def loop():
        rank = session.get_world_rank()
        session.report_sharded({"step": 0}, _state_at(1),
                               extra={"step": 1})
        if rank == 0:
            # Rank 0's ack is queued, ahead of its next report ...
            session._get_session().wait_for_writer()
        session.report({"step": 1})
        # ... so it was taken in the round of that report, and the round
        # of this one has begun before rank 1's writer may go.
        session.report({"step": 2})
        if rank == 0:
            assert not commits
            gate.set()
        session.report({"step": 3})

    result = DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="rounds",
                             storage_path=str(tmp_path))).fit()
    assert len(commits) == 1
    ranks, metrics, handle, gate_was_open = commits[0]
    assert ranks == [0, 1] and metrics == {"step": 0} and gate_was_open
    assert handle is not None and handle.extra == {"step": 1}
    assert [m["step"] for m in result.metrics_history] == [0, 1, 2, 3]
    assert result.checkpoint.extra == {"step": 1}
    assert result.checkpoint.world_size == 2
    assert _trees_equal(result.checkpoint.load_full(), _state_at(1))
    assert [n for n in os.listdir(tmp_path) if n.endswith(".manifest")] \
        == [sc.manifest_filename("rounds", 1)]


def test_reshard_on_restart_disabled_refuses(ray_start_regular, tmp_path):
    """With train_reshard_on_restart off, a gang sized differently from
    the saved mesh refuses to resume (a config veto, not a retryable
    TrainingFailedError)."""
    backend = spill.FileSpillBackend(str(tmp_path))
    _, uri, _ = _save_sharded(backend, "frozen", 1, _state_at(1), 2,
                              extra={"step": 1})
    ck = ShardedCheckpoint.from_manifest_uri(uri)
    executor = BackendExecutor(BackendConfig(),
                               ScalingConfig(num_workers=1))
    _set_flag("train_reshard_on_restart", False)
    try:
        with pytest.raises(RuntimeError,
                           match="train_reshard_on_restart"):
            executor._reshard_accounting(ck, new_world=1)
        # Same-size resume is always allowed.
        executor._reshard_accounting(ck, new_world=2)
    finally:
        _set_flag("train_reshard_on_restart", True)


if __name__ == "__main__":
    sys.exit(pytest.main(["-v", "-x", __file__]))
