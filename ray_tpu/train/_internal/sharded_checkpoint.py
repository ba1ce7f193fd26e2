"""Per-rank sharded train checkpoints with two-phase commit + reshard.

The t5x/Orbax-shaped answer to the single-writer checkpoint bottleneck:
the GSPMD layout that shards parameters across the mesh also shards the
*checkpoint* across ranks. Each rank persists only its local
parameter/optimizer blocks through a spill backend
(``train-<run>-ckpt-<seq>.shard-<rank>`` files, N parallel crash-safe
writes), and the save commits in two phases:

1. every rank writes its shard (atomic tmp → fsync → rename through
   :mod:`ray_tpu._private.spill`): the train loop waits only while the
   state leaves the device (:func:`gather_shard`); checksum, write, fsync
   and rename run on a writer thread beside the next steps
   (:func:`write_gathered`), which then acks the shard to the driver
   through the result gather;
2. only after ALL shard acks does the driver write the **manifest**
   (``train-<run>-ckpt-<seq>.manifest`` — param tree structure, per-param
   spec, mesh shape, shard → file map with per-block byte offsets and
   crc32 checksums).

The manifest IS the commit record: a rank SIGKILLed mid-save can never
leave a torn checkpoint, because a shard set without a manifest is
invisible to ``CheckpointManager.latest()`` and garbage-collected on
the next index load (``_gc_orphans``).

Resharding: block boundaries are balanced ``array_split`` bounds
(:func:`ray_tpu.parallel.sharding.axis_split_bounds`), so a checkpoint
saved on 8 ranks restores onto 6 or 4 without divisibility constraints —
:meth:`ShardedCheckpoint.load_for_rank` computes the new rank's index
block per parameter and pulls only the overlapping **byte ranges** from
each saved shard (``SpillBackend.read_range``; a contiguous-rows fast
path when only dim 0 is sharded), reassembling arrays that are
numerically identical to the originals.
"""

from __future__ import annotations

import json
import logging
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu._private import chaos, spill
from ray_tpu._private.ray_config import runtime_config_value
from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.parallel.sharding import (axis_split_bounds,  # noqa: F401
                                       shard_slices, slices_overlap)
from ray_tpu.util import tracing

logger = logging.getLogger("ray_tpu.train")

MANIFEST_FORMAT = "ray_tpu-sharded-ckpt-v1"

#: axes_items: ordered [(mesh_axis_name, size), ...]; rank -> coords is
#: row-major over this order, matching Mesh device enumeration.
AxesItems = Sequence[Tuple[str, int]]


def _shard_parallelism() -> int:
    return max(1, int(runtime_config_value("train_ckpt_shard_parallelism",
                                           8)))


def verify_checksums_default() -> bool:
    return bool(runtime_config_value("train_ckpt_verify_checksums", True))


# ---------------------------------------------------------------------------
# File naming
# ---------------------------------------------------------------------------


def ckpt_prefix(run: str) -> str:
    return f"train-{run}-ckpt-"


def shard_filename(run: str, seq: int, rank: int) -> str:
    return f"train-{run}-ckpt-{seq:06d}.shard-{rank:04d}"


def manifest_filename(run: str, seq: int) -> str:
    return f"train-{run}-ckpt-{seq:06d}.manifest"


def is_shard_file(name: str) -> bool:
    return ".shard-" in name


def is_manifest_file(name: str) -> bool:
    return name.endswith(".manifest")


# ---------------------------------------------------------------------------
# Pytree flatten/unflatten (JSON-serializable structure skeleton)
# ---------------------------------------------------------------------------


def flatten_tree(tree: Any) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Flatten a nested dict/list/tuple of array leaves into
    ``{"a/b/0": leaf}`` plus a JSON skeleton that rebuilds the exact
    container types (dict keys are coerced to str)."""
    flat: Dict[str, Any] = {}

    def rec(node: Any, path: Tuple[str, ...]) -> Dict[str, Any]:
        if isinstance(node, dict):
            return {"kind": "dict",
                    "children": {str(k): rec(node[k], path + (str(k),))
                                 for k in sorted(node, key=str)}}
        if isinstance(node, (list, tuple)):
            kind = "list" if isinstance(node, list) else "tuple"
            return {"kind": kind,
                    "children": [rec(v, path + (str(i),))
                                 for i, v in enumerate(node)]}
        flat["/".join(path)] = node
        return {"kind": "leaf"}

    structure = rec(tree, ())
    return flat, structure


def unflatten_tree(structure: Dict[str, Any],
                   flat: Dict[str, Any]) -> Any:
    def rec(skel: Dict[str, Any], path: Tuple[str, ...]) -> Any:
        kind = skel["kind"]
        if kind == "leaf":
            return flat["/".join(path)]
        if kind == "dict":
            return {k: rec(c, path + (k,))
                    for k, c in skel["children"].items()}
        vals = [rec(c, path + (str(i),))
                for i, c in enumerate(skel["children"])]
        return vals if kind == "list" else tuple(vals)

    return rec(structure, ())


# ---------------------------------------------------------------------------
# Specs / mesh coordinates
# ---------------------------------------------------------------------------


def normalize_spec(spec: Any, ndim: int) -> List[List[str]]:
    """Per-dim spec entry → list of mesh axis names (JSON form).
    Accepts a ``jax.sharding.PartitionSpec``, tuple/list, or None
    (fully replicated)."""
    entries = list(spec) if spec is not None else []
    out: List[List[str]] = []
    for d in range(ndim):
        e = entries[d] if d < len(entries) else None
        if e is None:
            out.append([])
        elif isinstance(e, str):
            out.append([e])
        else:
            out.append([str(a) for a in e])
    return out


def default_specs(flat: Dict[str, Any], axis: str = "fsdp"
                  ) -> Dict[str, List[List[str]]]:
    """FSDP-style default: shard dim 0 of every >=1-d leaf over ``axis``
    (the ZeRO-3 analog); scalars stay replicated."""
    specs = {}
    for path, leaf in flat.items():
        # np.ndim reads a device array's own ``ndim``: no gather here.
        specs[path] = [[axis] if d == 0 else []
                       for d in range(np.ndim(leaf))]
    return specs


def rank_coords(rank: int, axes_items: AxesItems) -> Dict[str, int]:
    """Row-major rank → per-axis mesh coordinates."""
    sizes = [int(s) for _, s in axes_items]
    idx = list(np.unravel_index(rank, sizes)) if sizes else []
    return {name: int(i) for (name, _), i in zip(axes_items, idx)}


def world_size_of(axes_items: AxesItems) -> int:
    n = 1
    for _, s in axes_items:
        n *= int(s)
    return n


# ---------------------------------------------------------------------------
# Block bytes and checksums
# ---------------------------------------------------------------------------

_CRC_POLY = 0xEDB88320  # zlib's CRC-32, bits reflected
#: Bytes of one column panel of a relayout: small enough to stay in cache.
_PANEL_BYTES = 1 << 20
#: Threads that share a relayout's panels; on a v5e host 413 MB take 1.15 s
#: on one, 0.40 s on four and 0.30 s on eight (PERF.md, PR 24).
_RELAYOUT_THREADS = 8
_UINT = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}
#: Bytes a shard's writer lets pile up before it waits for the disk: at a
#: save of 4.86 GB every 11 s three saves' worth of unflushed pages made one
#: fsync take 11.7 s (PERF.md, PR 50); flushed as it goes, 0.1-2 s a save.
_SYNC_BYTES = 512 << 20


def _crc_mul(a: int, b: int) -> int:
    """``a * b`` modulo the CRC polynomial (reflected: bit 31 is x^0)."""
    product = 0
    bit = 1 << 31
    while a:
        if a & bit:
            product ^= b
            a ^= bit
        bit >>= 1
        b = (b >> 1) ^ _CRC_POLY if b & 1 else b >> 1
    return product


def _crc_x_pow_2n() -> List[int]:
    powers = [1 << 30]  # x^1
    while len(powers) < 32:
        powers.append(_crc_mul(powers[-1], powers[-1]))
    return powers


#: x^(2^n) modulo the polynomial; the order of x is 2^32 - 1, so n wraps.
_CRC_X_POW_2N = _crc_x_pow_2n()


def crc32_combine(crc1: int, crc2: int, len2: int) -> int:
    """``zlib.crc32(a + b)`` from ``zlib.crc32(a)``, ``zlib.crc32(b)`` and
    ``len(b)``: appending ``len2`` bytes multiplies ``crc1`` by
    x^(8 * len2), so a file's checksum follows from its blocks' without a
    second pass over the bytes."""
    shift = 1 << 31  # x^0
    n = 3  # 8 * len2 = len2 * 2^3
    while len2:
        if len2 & 1:
            shift = _crc_mul(_CRC_X_POW_2N[n & 31], shift)
        len2 >>= 1
        n += 1
    return _crc_mul(shift, crc1 & 0xFFFFFFFF) ^ (crc2 & 0xFFFFFFFF)


def _local_block(a: np.ndarray, spec: Any, axes: Dict[str, int],
                 coords: Dict[str, int]) -> np.ndarray:
    """A rank's index block of a leaf on the host: a view, in the memory
    order the leaf arrived in. On a real multi-controller mesh the slice
    of a jax array resolves from the rank's addressable shards; on
    CPU/replicated state it is a plain numpy slice — either way only 1/N
    of the bytes survive."""
    return a[shard_slices(a.shape, normalize_spec(spec, a.ndim), axes,
                          coords)]


def _c_order_copy(block: np.ndarray) -> Tuple[np.ndarray, str]:
    """A C-contiguous copy of a block that is not, and what made it:
    ``relayout`` where the block is whole but in the transposed memory
    order (a leaf can come off the device column-major), copied by column
    panels that fit the cache, on a few threads (numpy copies with the
    GIL released): many times faster than the strided copy; ``slice`` for
    anything else (a rank's block along a non-leading dim). Copied as
    plain integers of the item's size: an extension dtype (bfloat16) has
    no fast strided loop of its own."""
    raw = block.view(_UINT.get(block.dtype.itemsize, block.dtype))
    if block.ndim < 2 or not block.T.flags.c_contiguous:
        return np.ascontiguousarray(raw).view(block.dtype), "slice"
    out = np.empty(raw.shape, raw.dtype)
    cols = raw.shape[-1]
    step = max(1, _PANEL_BYTES * cols // raw.nbytes)

    def copy_panel(j: int) -> None:
        out[..., j:j + step] = raw[..., j:j + step]

    with ThreadPoolExecutor(max_workers=_RELAYOUT_THREADS) as pool:
        list(pool.map(copy_panel, range(0, cols, step)))
    return out.view(block.dtype), "relayout"


def extract_local_shard(flat: Dict[str, Any],
                        specs: Dict[str, Any],
                        axes_items: AxesItems,
                        rank: int) -> Dict[str, np.ndarray]:
    """This rank's index block of every leaf, C-contiguous: the arrays
    whose bytes :func:`write_shard` puts into the rank's shard file."""
    axes = dict(axes_items)
    coords = rank_coords(rank, axes_items)
    out = {}
    for path, leaf in flat.items():
        block = _local_block(np.asarray(leaf), specs.get(path), axes,
                             coords)
        out[path] = block if block.flags.c_contiguous \
            else _c_order_copy(block)[0]
    return out


# ---------------------------------------------------------------------------
# Shard write (runs in the rank's worker process)
# ---------------------------------------------------------------------------


def gather_shard(run: str, seq: int, rank: int, flat: Dict[str, Any],
                 specs: Dict[str, Any], axes_items: AxesItems,
                 detach: bool = False) -> Dict[str, np.ndarray]:
    """The first half of a rank's shard write, the only one that needs the
    state: this rank's index block of every leaf, on the host, as a view in
    the memory order the leaf arrived in (:func:`write_gathered` takes
    them from here). Every device-to-host transfer is started before the
    first leaf is read, so the runtime moves them all at once. A device
    leaf is not copied again: the array ``np.asarray`` gives is the
    transfer's own host memory, and stays valid when the device buffer is
    donated or deleted. With ``detach`` a host-resident leaf (numpy, a
    scalar) has its block copied, in the order it lies in, so that the
    caller may change it once this returns.

    Spans, recorded under ``train::report_sharded`` only:
    ``ckpt::prefetch``, then per leaf ``ckpt::gather`` (the wait for its
    transfer).

    Chaos sites, before the first byte: ``train.ckpt_shard_write_error``
    (``io_oserror`` — surfaces as :class:`spill.SpillFailure`, failing
    this save attempt cleanly) and ``train.ckpt_shard_kill`` (``kill`` —
    the SIGKILL-mid-save stand-in; :class:`chaos.ChaosKill` propagates so
    the rank can play dead with its shard unwritten).
    """
    try:
        if chaos.ACTIVE:
            chaos.maybe_inject("train.ckpt_shard_kill")
            chaos.maybe_inject("train.ckpt_shard_write_error")
    except chaos.ChaosKill:
        raise
    except OSError as exc:
        raise spill.SpillFailure(
            f"shard write of {shard_filename(run, seq, rank)} failed: "
            f"{exc}") from exc
    axes = dict(axes_items)
    coords = rank_coords(rank, axes_items)
    paths = sorted(flat)
    on_device = {p for p in paths if hasattr(flat[p], "copy_to_host_async")}
    with tracing.child_span("ckpt::prefetch") as span:
        # Device arrays only: numpy and scalar leaves are on the host.
        for path in paths:
            if path in on_device:
                flat[path].copy_to_host_async()
        if span is not None:
            span.attributes.update(leaves=len(on_device))
    blocks: Dict[str, np.ndarray] = {}
    for path in paths:
        with tracing.child_span("ckpt::gather") as span:
            a = np.asarray(flat[path])
            block = _local_block(a, specs.get(path), axes, coords)
            if detach and path not in on_device:
                block = np.array(block, order="K")
            blocks[path] = block
            if span is not None:
                span.attributes.update(leaf=path, bytes=a.nbytes)
    return blocks


def write_gathered(backend: spill.SpillBackend, run: str, seq: int,
                   rank: int, blocks: Dict[str, np.ndarray]
                   ) -> Dict[str, Any]:
    """The second half of a rank's crash-safe shard write: host memory to
    the file, with nothing of the state in hand but :func:`gather_shard`'s
    blocks, so it runs as well on a thread beside the train loop. The
    shard file is the pure concatenation of C-order blocks (one per leaf,
    sorted by path); all metadata — offsets, shapes, checksums — rides the
    returned record into the manifest, so a byte-range reader never parses
    the file.

    Each byte is touched twice, by its block's CRC and by the ``write``,
    both on the array's own memory; the file's CRC is combined from the
    blocks'. A block is copied only where it is not C-contiguous as it
    arrived (:func:`_c_order_copy`). The writer waits for the disk every
    ``_SYNC_BYTES`` it has written (``SpillWriter.sync``), so the unflushed
    part of the file stays bounded, and the file is fsynced and renamed
    before this returns (``SpillBackend.open_writer``); an ``OSError``
    anywhere leaves no file and raises :class:`spill.SpillFailure`.

    Spans, recorded under ``train::report_sharded`` only, one after the
    other on this thread, per leaf: ``ckpt::copy`` (only where bytes are
    copied; ``what`` is ``slice`` or ``relayout``), ``ckpt::checksum``,
    ``ckpt::write`` (and one with ``what`` = ``sync`` where the writer
    waits for the disk); a last ``ckpt::write`` for fsync and rename.
    """
    filename = shard_filename(run, seq, rank)
    meta: Dict[str, Dict[str, Any]] = {}
    offset = synced = 0
    file_crc = 0
    write_s = 0.0  # seconds in file writes, syncs, fsync and rename

    def timed(op, *args, **attributes):
        nonlocal write_s
        with tracing.child_span("ckpt::write") as span:
            t0 = time.perf_counter()
            result = op(*args)
            write_s += time.perf_counter() - t0
            if span is not None:
                span.attributes.update(attributes)
        return result

    with backend.open_writer(filename) as writer:
        for path in sorted(blocks):
            block = blocks[path]
            if not block.flags.c_contiguous:
                with tracing.child_span("ckpt::copy") as span:
                    block, what = _c_order_copy(block)
                    if span is not None:
                        span.attributes.update(leaf=path, what=what,
                                               bytes=block.nbytes)
            with tracing.child_span("ckpt::checksum") as span:
                # memoryview() refuses bfloat16; reshape keeps 0-d leaves.
                raw = block.reshape(-1).view(np.uint8)
                block_crc = zlib.crc32(raw) & 0xFFFFFFFF
                file_crc = crc32_combine(file_crc, block_crc, raw.nbytes)
                meta[path] = {
                    "offset": offset,
                    "length": raw.nbytes,
                    "crc32": block_crc,
                    "shape": [int(s) for s in block.shape],
                    "dtype": str(block.dtype),
                }
                if span is not None:
                    span.attributes.update(leaf=path, bytes=raw.nbytes)
            timed(writer.write, raw, leaf=path, bytes=raw.nbytes)
            offset += raw.nbytes
            if offset - synced >= _SYNC_BYTES:
                timed(writer.sync, what="sync", bytes=offset - synced)
                synced = offset
        uri = timed(writer.commit, what="commit", bytes=offset,
                    seq=int(seq), rank=int(rank))
    try:
        from ray_tpu._private import builtin_metrics
        builtin_metrics.train_ckpt_shard_bytes().inc(
            offset, tags={"rank": str(rank)})
    except Exception:  # noqa: BLE001 - accounting never breaks a save
        pass
    return {"seq": int(seq), "rank": int(rank), "file": filename,
            "uri": uri, "bytes": offset,
            "crc32": file_crc, "blocks": meta,
            "write_s": round(write_s, 6)}


def write_shard(backend: spill.SpillBackend, run: str, seq: int, rank: int,
                flat: Dict[str, Any], specs: Dict[str, Any],
                axes_items: AxesItems) -> Dict[str, Any]:
    """One rank's crash-safe shard write, both halves on the caller's
    thread: :func:`gather_shard` (the state leaves the device), then
    :func:`write_gathered` (checksum, write, fsync, rename). On return the
    shard file exists under its final name; it belongs to a checkpoint
    only once a manifest that names it is committed. ``report_sharded``
    runs the first half in the train loop and the second beside it."""
    return write_gathered(
        backend, run, seq, rank,
        gather_shard(run, seq, rank, flat, specs, axes_items))


def build_tree_meta(flat: Dict[str, Any], structure: Dict[str, Any],
                    specs: Dict[str, Any], axes_items: AxesItems,
                    extra: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    """The global (rank-independent) half of a manifest; identical on
    every rank, so the driver takes rank 0's copy."""
    params = {}
    norm_specs = {}
    for path, leaf in flat.items():
        a = np.asarray(leaf)
        params[path] = {"shape": [int(s) for s in a.shape],
                        "dtype": str(a.dtype)}
        norm_specs[path] = normalize_spec(specs.get(path), a.ndim)
    return {
        "mesh": [[name, int(size)] for name, size in axes_items],
        "world_size": world_size_of(axes_items),
        "params": params,
        "specs": norm_specs,
        "structure": structure,
        "extra": dict(extra or {}),
    }


# ---------------------------------------------------------------------------
# Manifest (the commit record — written LAST, by the driver)
# ---------------------------------------------------------------------------


def build_manifest(run: str, seq: int, tree_meta: Dict[str, Any],
                   shard_records: List[Dict[str, Any]]) -> Dict[str, Any]:
    shards = sorted(
        ({k: rec[k] for k in ("rank", "file", "bytes", "crc32", "blocks")}
         for rec in shard_records), key=lambda r: r["rank"])
    manifest = {"format": MANIFEST_FORMAT, "run": run, "seq": int(seq)}
    manifest.update(tree_meta)
    manifest["shards"] = shards
    return manifest


def write_manifest(backend: spill.SpillBackend, run: str, seq: int,
                   manifest: Dict[str, Any]) -> str:
    return backend.write(manifest_filename(run, seq),
                         json.dumps(manifest).encode())


def read_manifest(uri: str) -> Optional[Dict[str, Any]]:
    raw = spill.read_uri(uri)
    if raw is None:
        return None
    try:
        manifest = json.loads(raw.decode())
    except (ValueError, UnicodeDecodeError):
        return None
    if manifest.get("format") != MANIFEST_FORMAT:
        return None
    return manifest


def validate_shards(backend: spill.SpillBackend,
                    manifest: Dict[str, Any],
                    verify_checksums: bool) -> bool:
    """Are all of a manifest's shard files present, full-size, and
    (optionally) checksum-clean? Drives orphan-GC adoption/removal of
    manifests whose index entry was lost."""
    for shard in manifest.get("shards", []):
        uri = backend.uri_for(shard["file"])
        size = backend.size_of(uri)
        if size is None or size < int(shard["bytes"]):
            return False
        if verify_checksums:
            data = backend.read(uri, expected_size=int(shard["bytes"]))
            if data is None or \
                    (zlib.crc32(data) & 0xFFFFFFFF) != int(shard["crc32"]):
                return False
    return True


# ---------------------------------------------------------------------------
# The restore/reshard handle
# ---------------------------------------------------------------------------


class ShardedCheckpoint(Checkpoint):
    """A committed sharded checkpoint: a manifest plus byte-range access
    to its shard files. Cheap to ship to every rank of a (re)started
    gang — nothing is read until ``load_for_rank``/``load_full``.

    ``to_dict()`` returns the small user ``extra`` dict (step counters
    etc.); parameter state comes back through :meth:`load_for_rank`
    (the rank's block under the NEW mesh — the reshard path when the
    gang shrank or grew) or :meth:`load_full`.
    """

    def __init__(self, manifest: Dict[str, Any], uri: str):
        super().__init__(uri=uri)
        self.manifest = manifest

    @classmethod
    def from_manifest_uri(cls, uri: str) -> "ShardedCheckpoint":
        manifest = read_manifest(uri)
        if manifest is None:
            raise ValueError(
                f"no readable sharded-checkpoint manifest at {uri}")
        return cls(manifest, uri)

    # -- metadata ---------------------------------------------------------

    @property
    def seq(self) -> int:
        return int(self.manifest["seq"])

    @property
    def world_size(self) -> int:
        return int(self.manifest["world_size"])

    @property
    def mesh_axes(self) -> List[Tuple[str, int]]:
        return [(name, int(size)) for name, size in self.manifest["mesh"]]

    @property
    def extra(self) -> Dict[str, Any]:
        return dict(self.manifest.get("extra", {}))

    def to_dict(self) -> Dict[str, Any]:
        return self.extra

    @property
    def extra_metadata(self) -> Dict[str, Any]:
        return self.extra

    def _hydrate(self) -> None:
        raise ValueError(
            "sharded checkpoints have no monolithic payload; restore "
            "state with load_for_rank()/load_full()")

    _payload_bytes = _hydrate

    # -- restore / reshard ------------------------------------------------

    def _new_axes(self, world_size: Optional[int],
                  axes_items: Optional[AxesItems]) -> List[Tuple[str, int]]:
        if axes_items is not None:
            return [(n, int(s)) for n, s in axes_items]
        old = self.mesh_axes
        if world_size is None or world_size == self.world_size:
            return old
        sharded = [n for n, s in old if s > 1]
        if len(sharded) > 1:
            raise ValueError(
                f"cannot infer a {world_size}-rank mesh from saved axes "
                f"{old}: more than one sharded axis — pass axes_items")
        axis = sharded[0] if sharded else (old[0][0] if old else "fsdp")
        return [(n, world_size if n == axis else 1) for n, s in old] or \
            [(axis, world_size)]

    def load_for_rank(self, rank: int, world_size: Optional[int] = None,
                      axes_items: Optional[AxesItems] = None,
                      verify: Optional[bool] = None) -> Any:
        """This rank's local state under the NEW mesh: per parameter,
        compute the rank's index block and pull only the overlapping
        byte ranges from the saved shards. world_size == saved world is
        a plain per-rank reload; anything else is a reshard."""
        new_axes = self._new_axes(world_size, axes_items)
        if world_size is not None and world_size_of(new_axes) != world_size:
            raise ValueError(
                f"axes {new_axes} describe {world_size_of(new_axes)} "
                f"ranks, not {world_size}")
        return self._load_local(new_axes, rank, verify)

    def load_full(self, verify: Optional[bool] = None) -> Any:
        """The whole tree, reassembled (rank 0 of a 1-rank mesh)."""
        axes = [(name, 1) for name, _ in self.mesh_axes] or [("fsdp", 1)]
        return self._load_local(axes, 0, verify)

    def restore_on_mesh(self, mesh, rules=None, spec_tree=None) -> Any:
        """Reassemble and ``device_put`` under a new jax mesh — the
        single-controller reshard path (multi-controller ranks use
        ``load_for_rank`` and place their own block)."""
        from ray_tpu.parallel.sharding import shard_tree, tree_shardings
        tree = self.load_full()
        if spec_tree is None:
            import jax
            from jax.sharding import PartitionSpec
            flat, _ = flatten_tree(tree)
            specs = {p: PartitionSpec(*[tuple(e) if len(e) > 1 else
                                        (e[0] if e else None)
                                        for e in self.manifest["specs"][p]])
                     for p in flat}
            spec_tree = unflatten_tree(self.manifest["structure"], specs)
            del jax, tree_shardings
        return shard_tree(tree, mesh, spec_tree)

    # -- internals --------------------------------------------------------

    def _load_local(self, new_axes: List[Tuple[str, int]], rank: int,
                    verify: Optional[bool]) -> Any:
        with tracing.start_span("ckpt::restore") as span:
            flat = self._read_blocks(new_axes, rank, verify)
            if span is not None:
                span.attributes.update(
                    seq=self.seq, rank=rank,
                    bytes=sum(a.nbytes for a in flat.values()))
            return unflatten_tree(self.manifest["structure"], flat)

    def _read_blocks(self, new_axes: List[Tuple[str, int]], rank: int,
                     verify: Optional[bool]) -> Dict[str, np.ndarray]:
        """``{path: array}`` of this rank's blocks under ``new_axes``."""
        verify = verify_checksums_default() if verify is None else verify
        backend = spill.reader_for_uri(self._uri)
        if backend is None:
            raise ValueError(f"no spill backend can read {self._uri}")
        manifest = self.manifest
        old_axes = self.mesh_axes
        axes = dict(new_axes)
        coords = rank_coords(rank, new_axes)
        old_coord_cache = {s["rank"]: rank_coords(s["rank"], old_axes)
                           for s in manifest["shards"]}

        def load_param(path: str) -> np.ndarray:
            meta = manifest["params"][path]
            shape = tuple(meta["shape"])
            dtype = np.dtype(meta["dtype"])
            spec = manifest["specs"][path]
            sel = shard_slices(shape, spec, axes, coords)
            out = np.empty(tuple(s.stop - s.start for s in sel), dtype)
            for shard in manifest["shards"]:
                old_sl = shard_slices(shape, spec, dict(old_axes),
                                      old_coord_cache[shard["rank"]])
                ov = slices_overlap(sel, old_sl)
                if ov is None:
                    continue
                block = shard["blocks"][path]
                local_shape = tuple(s.stop - s.start for s in old_sl)
                uri = backend.uri_for(shard["file"])
                dest = tuple(slice(o.start - s.start, o.stop - s.start)
                             for o, s in zip(ov, sel))
                src = tuple(slice(o.start - s.start, o.stop - s.start)
                            for o, s in zip(ov, old_sl))
                whole = all(o == s for o, s in zip(ov, old_sl))
                rows_only = shape and all(
                    o == s for o, s in zip(ov[1:], old_sl[1:]))
                if whole or not rows_only:
                    # Whole block (also the general multi-dim fallback:
                    # read the block, slice in memory).
                    raw = backend.read_range(uri, int(block["offset"]),
                                             int(block["length"]))
                    if raw is None:
                        raise ValueError(
                            f"shard {shard['file']} unreadable for "
                            f"{path} (storage lost after commit?)")
                    if verify and (zlib.crc32(raw) & 0xFFFFFFFF) != \
                            int(block["crc32"]):
                        raise ValueError(
                            f"checksum mismatch in {shard['file']} "
                            f"block {path} — corrupt shard")
                    arr = np.frombuffer(raw, dtype).reshape(local_shape)
                    out[dest] = arr[src]
                else:
                    # Contiguous-rows fast path: only dim 0 differs, so
                    # the overlap is a contiguous byte range.
                    row_bytes = dtype.itemsize * int(
                        np.prod(local_shape[1:], dtype=np.int64))
                    lo = (ov[0].start - old_sl[0].start) * row_bytes
                    nrows = ov[0].stop - ov[0].start
                    raw = backend.read_range(
                        uri, int(block["offset"]) + lo, nrows * row_bytes)
                    if raw is None:
                        raise ValueError(
                            f"shard {shard['file']} unreadable for "
                            f"{path} (storage lost after commit?)")
                    arr = np.frombuffer(raw, dtype).reshape(
                        (nrows,) + local_shape[1:])
                    out[dest] = arr[(slice(None),) + src[1:]]
            return out

        paths = sorted(manifest["params"])
        flat: Dict[str, np.ndarray] = {}
        workers = min(_shard_parallelism(), max(1, len(paths)))
        if workers > 1 and len(paths) > 1:
            with ThreadPoolExecutor(max_workers=workers) as pool:
                for path, arr in zip(paths, pool.map(load_param, paths)):
                    flat[path] = arr
        else:
            for path in paths:
                flat[path] = load_param(path)
        return flat

    def __repr__(self):
        return (f"ShardedCheckpoint(id={self.id}, run="
                f"{self.manifest.get('run')!r}, seq={self.seq}, "
                f"world={self.world_size}, source={self._uri})")
