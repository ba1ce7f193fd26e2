"""Device mesh construction: the TPU-native parallelism substrate.

Where the reference wires torch DDP/FSDP process groups over NCCL
(reference: python/ray/train/torch/train_loop_utils.py:51 prepare_model,
train/torch/config.py:113 init_process_group), this framework expresses ALL
intra-model parallelism as a `jax.sharding.Mesh` with named axes and lets
XLA/GSPMD insert the collectives over ICI/DCN:

* ``dp``   — pure data parallelism (gradient psum)
* ``fsdp`` — fully-sharded data parallelism (ZeRO-3-equivalent: params and
             optimizer state sharded over this axis, all-gathered per layer)
* ``tp``   — tensor (Megatron-style model) parallelism
* ``sp``   — sequence/context parallelism (ring attention / Ulysses)
* ``ep``   — expert parallelism for MoE layers
* ``pp``   — pipeline parallelism (GPipe schedule over shard_map +
             ppermute, parallel/pipeline.py)

Batch dimensions shard over (dp, fsdp); weights over (fsdp, tp); sequence
over sp; experts over ep; pipeline stages over pp.
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import numpy as np

AXIS_ORDER = ("dp", "fsdp", "tp", "sp", "ep", "pp")
# Axes over which a batch is sharded.
BATCH_AXES = ("dp", "fsdp")


@dataclass(frozen=True)
class MeshConfig:
    """Sizes for each mesh axis; -1 means "fill with remaining devices".

    Axis order follows ICI-locality best practice: the innermost axes (tp,
    sp) get the most tightly coupled devices, dp/fsdp span slices/hosts (the
    scaling-book recipe: model axes ride ICI, data axes can ride DCN).
    """

    dp: int = 1
    fsdp: int = -1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1
    #: TPU pod slices joined over DCN (multi-slice training). The dp axis
    #: is the one that crosses the slice boundary — gradient psums ride
    #: DCN once per step while fsdp/tp/sp collectives stay on each
    #: slice's ICI (the scaling-book layering; SURVEY §2.4 "DCN-aware
    #: multi-slice meshes"). dp must be a multiple of `slices`.
    slices: int = 1

    def resolve(self, n_devices: int) -> "MeshConfig":
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        fills = [a for a, s in sizes.items() if s == -1]
        if len(fills) > 1:
            raise ValueError(f"Only one axis may be -1, got {fills}")
        known = math.prod(s for s in sizes.values() if s != -1)
        if fills:
            if n_devices % known != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {known}")
            sizes[fills[0]] = n_devices // known
        total = math.prod(sizes.values())
        if total != n_devices:
            raise ValueError(
                f"Mesh axes {sizes} use {total} devices but {n_devices} "
                "are available")
        if self.slices > 1 and sizes["dp"] % self.slices != 0:
            raise ValueError(
                f"dp={sizes['dp']} must be a multiple of slices="
                f"{self.slices}: the dp axis is the one crossing the "
                "DCN slice boundary")
        return MeshConfig(**sizes, slices=self.slices)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return AXIS_ORDER

    def shape(self) -> Tuple[int, ...]:
        return tuple(getattr(self, a) for a in AXIS_ORDER)

    @property
    def batch_shards(self) -> int:
        return self.dp * self.fsdp


def build_mesh(config: Optional[MeshConfig] = None,
               devices: Optional[Sequence] = None):
    """Build a `jax.sharding.Mesh` from a MeshConfig.

    `mesh_utils.create_device_mesh` lays the axes over the platform
    topology (tp/sp land on ICI neighbours; virtual CPU devices have none
    and keep enumeration order). A shape the topology cannot hold raises:
    a mesh silently laid out against the wiring would only show as slow
    collectives.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    from ray_tpu._private.jax_compat import enable_compile_cache

    enable_compile_cache()
    if devices is None:
        devices = jax.devices()
    config = (config or MeshConfig()).resolve(len(devices))
    if config.slices > 1:
        return _build_multi_slice_mesh(config, list(devices))
    return Mesh(mesh_utils.create_device_mesh(
        config.shape(), devices=list(devices)), AXIS_ORDER)


def _build_multi_slice_mesh(config: MeshConfig, devices: list):
    """Hybrid DCN x ICI mesh (the multi-slice analog of the reference's
    multi-node NCCL world): the OUTER positions of the dp axis enumerate
    slices, so only dp collectives (gradient psum) cross DCN; every
    fsdp/tp/sp/ep/pp collective stays inside one slice's ICI. Devices
    group by their hardware ``slice_index`` when the platform reports it
    (real multi-slice TPU), falling back to contiguous equal splits
    (virtual/CPU validation meshes)."""
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    n_slices = config.slices
    if len(devices) % n_slices != 0:
        raise ValueError(
            f"{len(devices)} devices not divisible into {n_slices} slices")
    per_slice = len(devices) // n_slices
    by_slice: dict = {}
    if all(getattr(d, "slice_index", None) is not None for d in devices):
        for d in devices:
            by_slice.setdefault(d.slice_index, []).append(d)
        if len(by_slice) != n_slices or any(
                len(v) != per_slice for v in by_slice.values()):
            raise ValueError(
                f"hardware reports {len(by_slice)} slices with sizes "
                f"{[len(v) for v in by_slice.values()]}, config wants "
                f"{n_slices} x {per_slice}")
        groups = [by_slice[k] for k in sorted(by_slice)]
    else:
        groups = [devices[i * per_slice:(i + 1) * per_slice]
                  for i in range(n_slices)]
    # Arrange each slice's devices over (dp_in, fsdp, tp, sp, ep, pp),
    # then stack slices as the OUTER dp positions.
    dp_in = config.dp // n_slices
    inner_shape = (dp_in, config.fsdp, config.tp, config.sp,
                   config.ep, config.pp)
    slabs = [mesh_utils.create_device_mesh(inner_shape, devices=group)
             for group in groups]
    dev_array = np.stack(slabs, axis=0).reshape(config.shape())
    return Mesh(dev_array, AXIS_ORDER)


def single_device_mesh():
    """A 1-device mesh with all axes size 1 — lets the same sharded program
    run unmodified on one chip."""
    return build_mesh(MeshConfig(dp=1, fsdp=1, tp=1, sp=1, ep=1))


# -- current-mesh registry ----------------------------------------------
# Ops that need an explicit shard_map (the flash kernels, ring attention)
# read the ambient mesh here, and a model reads the sharding rules beside it
# to state where its activations live (sharding.constrain);
# make_train_step / user code set them. A registry rather than a parameter
# because both must be static at trace time while model code only receives
# (params, cfg, batch). Tracing runs on the calling thread, so the registry
# is per thread: two actors stepping over different chips in one process
# never see each other's mesh.

_current = threading.local()


def set_current_mesh(mesh, rules=None) -> None:
    """``rules``: the step's ShardingRules (None: the default table)."""
    _current.mesh = mesh
    _current.rules = rules


def current_mesh():
    return getattr(_current, "mesh", None)


def current_rules():
    return getattr(_current, "rules", None)
