"""Node resource detection — TPU chips as first-class resources.

The reference autodetects GPUs and assigns CUDA_VISIBLE_DEVICES
(python/ray/_private/resource_spec.py:175 _autodetect_num_gpus). Here the
accelerator layer is TPU-native: chips are counted from the host's PCI
devices and device nodes (``autodetect_num_tpus``), exposed as ``TPU`` plus an
``accelerator_type:TPU-<gen>`` marker resource.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, Optional


@dataclass
class NodeResources:
    num_cpus: float
    num_tpus: float
    memory_bytes: float
    tpu_platform: str = ""  # e.g. "tpu v4"
    tpu_topology: str = ""  # e.g. "2x2x1"
    custom: Dict[str, float] = field(default_factory=dict)

    def to_resource_map(self) -> Dict[str, float]:
        resources = {"CPU": self.num_cpus, "memory": self.memory_bytes}
        if self.num_tpus:
            resources["TPU"] = self.num_tpus
            if self.tpu_platform:
                marker = "accelerator_type:" + self.tpu_platform.upper().replace(" ", "-")
                resources[marker] = 1.0
        resources.update(self.custom)
        return resources


# How a TPU chip shows on its host: a PCI function with Google's vendor id
# and a per-generation device id (the table libtpu's own loader goes by),
# opened through /dev/vfio/<its IOMMU group> (v5e on) or /dev/accel<N>
# (up to v4). A host may list more chips on the bus than it was granted
# device nodes for, so the nodes are what is counted.
_SYSFS_PCI_DEVICES = "/sys/bus/pci/devices"
_DEV = "/dev"
_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICES = {
    "0x0027": "TPU v3", "0x005e": "TPU v4", "0x0062": "TPU v5p",
    "0x0063": "TPU v5 lite", "0x006f": "TPU v6 lite", "0x0076": "TPU7x",
}


def _read_sysfs(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return ""


def _jax_would_use_tpu() -> bool:
    """False when this process's JAX is pinned away from the TPU (the
    ``JAX_PLATFORMS`` variable, or ``jax_platforms`` set in code once jax
    is imported): chips it cannot drive are not this node's resources."""
    import sys
    platforms = os.environ.get("JAX_PLATFORMS", "")
    jax = sys.modules.get("jax")
    if jax is not None:
        platforms = jax.config.jax_platforms or platforms
    return not platforms or "tpu" in platforms.split(",")


def autodetect_num_tpus() -> tuple[float, str]:
    """Count the TPU chips this host may open, from sysfs and /dev.

    The probe reads directory entries only: it neither imports JAX nor
    opens a chip, so it is safe in any process. A chip serves one process
    at a time, and that process is the one that runs TPU tasks in its own
    threads: the driver in local mode, the node daemon under
    ``ray-tpu start`` (worker subprocesses are pinned to the CPU). JAX
    takes the chip there on first device use; counting must not take it
    earlier or elsewhere.

    ``RAY_TPU_NUM_CHIPS`` and ``TPU_VISIBLE_CHIPS`` fake or narrow the
    count; neither is needed on a TPU host.
    """
    env = os.environ.get("RAY_TPU_NUM_CHIPS")
    if env is not None:
        return float(env), os.environ.get("RAY_TPU_PLATFORM", "tpu")
    if not _jax_would_use_tpu():
        return 0.0, ""
    import glob
    kind, vfio_chips = "", 0
    for dev in glob.glob(os.path.join(_SYSFS_PCI_DEVICES, "*")):
        if _read_sysfs(os.path.join(dev, "vendor")) != _GOOGLE_PCI_VENDOR:
            continue
        dev_kind = _TPU_PCI_DEVICES.get(
            _read_sysfs(os.path.join(dev, "device")))
        if dev_kind is None:
            continue
        kind = dev_kind
        try:
            group = os.path.basename(
                os.readlink(os.path.join(dev, "iommu_group")))
        except OSError:
            continue
        vfio_chips += os.path.exists(os.path.join(_DEV, "vfio", group))
    chips = vfio_chips or len(
        glob.glob(os.path.join(_DEV, "accel[0-9]*")))
    if not kind or not chips:
        return 0.0, ""
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        chips = len([c for c in visible.split(",") if c.strip()])
    return float(chips), kind


def detect_node_resources(
    num_cpus: Optional[float] = None,
    num_tpus: Optional[float] = None,
    memory: Optional[float] = None,
    resources: Optional[Dict[str, float]] = None,
) -> NodeResources:
    if num_cpus is None:
        num_cpus = float(os.cpu_count() or 1)
    platform = ""
    if num_tpus is None:
        num_tpus, platform = autodetect_num_tpus()
    if memory is None:
        try:
            page = os.sysconf("SC_PAGE_SIZE")
            phys = os.sysconf("SC_PHYS_PAGES")
            memory = float(page * phys) * 0.7
        except (ValueError, OSError):
            memory = 8e9
    from ray_tpu._private.task_spec import validate_resource_name
    for name in (resources or {}):
        validate_resource_name(name)
    return NodeResources(
        num_cpus=float(num_cpus),
        num_tpus=float(num_tpus),
        memory_bytes=float(memory),
        tpu_platform=platform,
        custom=dict(resources or {}),
    )
