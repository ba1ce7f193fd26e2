"""chip_smoke.py off the chip: its phase functions at tiny size on the
CPU's virtual devices, its refusal to run without a TPU, and the pieces it
stands on (compile-cache location, chip discovery, chip-to-device mapping).
The real sizes run only through the chip tool (`python chip_smoke.py`).
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu._private import resource_spec

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # the train loop is pickled by reference
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.modules.pop(spec.name, None)


@pytest.fixture
def four_fake_chips():
    """TPU is a logical resource: four 'chips' here are the first four
    virtual CPU devices (the fake-TPU strategy of conftest.py)."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_tpus=4, _memory=1e9)
    yield
    ray_tpu.shutdown()


# seq 128 is one whole kernel tile, so attn_impl="flash" runs the Pallas
# kernels (interpreted), not the short-sequence blockwise path.
_TINY = dict(preset="gpt-tiny", seq=128, overrides={"attn_impl": "flash"})


def test_train_phase_on_cpu(chip_smoke, four_fake_chips):
    report = chip_smoke.train_phase(batch=4, steps=4, **_TINY)
    assert report["platform"] == "cpu"
    assert report["kernel_calls"] == 0  # interpret mode leaves no Mosaic call
    assert len(report["device_ids"]) == 1


def test_mesh_phase_on_cpu(chip_smoke, four_fake_chips):
    """One worker with four reserved chips gets a four-device mesh; the
    flash kernels run per shard under it and agree with one device."""
    report = chip_smoke.mesh_phase(batch=8, steps=3, **_TINY)
    assert len(report["sharded"]["device_ids"]) == 4
    assert len(report["single"]["device_ids"]) == 1


def test_serve_phase_on_cpu(chip_smoke, four_fake_chips):
    served = chip_smoke.serve_phase("unet-tiny", batch=4, steps=4,
                                    requests=9)
    assert served["answered"] == 9
    assert served["placement"]["platforms"] == ["cpu"]


def test_main_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_compile_cache_dir_is_fixed(tmp_path, monkeypatch):
    """In the checkout whatever the working directory (the module is
    loaded afresh from each), with no temporary name in it;
    JAX_COMPILATION_CACHE_DIR wins and is set nowhere else."""
    import importlib

    from ray_tpu._private import jax_compat
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = os.path.join(_ROOT, ".jax_cache")
    for cwd in (tmp_path, _ROOT):
        monkeypatch.chdir(cwd)
        assert importlib.reload(jax_compat).compile_cache_dir() == expected
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert jax_compat.compile_cache_dir() == "/some/dir"


def test_enable_compile_cache_sets_no_directory_over_the_variable(
        monkeypatch):
    """On the CPU backend (this process) the helper does nothing. On a TPU
    with the variable set it may tune thresholds but must not point the
    cache anywhere else."""
    import jax

    from ray_tpu._private import jax_compat
    before = jax.config.jax_compilation_cache_dir
    jax_compat.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    threshold = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        jax_compat.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          threshold)


def _fake_tpu_host(root, granted):
    """A sysfs/dev tree as a v5e host shows it: four chips on the PCI bus,
    each in its own IOMMU group, and a /dev/vfio node only for the groups
    this machine was granted."""
    pci, dev = root / "pci", root / "dev"
    (dev / "vfio").mkdir(parents=True)
    (dev / "vfio" / "vfio").touch()
    for group in range(4):
        chip = pci / f"0000:00:0{group + 4}.0"
        chip.mkdir(parents=True)
        (chip / "vendor").write_text("0x1ae0\n")
        (chip / "device").write_text("0x0063\n")
        (root / "iommu_groups" / str(group)).mkdir(parents=True)
        (chip / "iommu_group").symlink_to(
            root / "iommu_groups" / str(group))
        if group in granted:
            (dev / "vfio" / str(group)).touch()
    nic = pci / "0000:00:01.0"  # another Google device that is no TPU
    nic.mkdir()
    (nic / "vendor").write_text("0x1ae0\n")
    (nic / "device").write_text("0x0042\n")
    return str(pci), str(dev)


_INIT_SNIPPET = """
import json, sys
sys.path.insert(0, sys.argv[1])
from ray_tpu._private import resource_spec
resource_spec._SYSFS_PCI_DEVICES, resource_spec._DEV = sys.argv[2:4]
import ray_tpu
ray_tpu.init(num_cpus=2)
assert "jax" not in sys.modules, "init() imported jax"

@ray_tpu.remote(num_tpus=1)
class OneChip:
    def chips(self):
        return ray_tpu.get_tpu_ids()

actors = [OneChip.remote() for _ in range(2)]
print(json.dumps({
    "resources": ray_tpu.cluster_resources(),
    "chips": ray_tpu.get([a.chips.remote() for a in actors])}))
ray_tpu.shutdown()
"""


def test_init_finds_chips_without_jax_and_actors_get_their_own(tmp_path):
    """A fake two-chip node: a plain init() in a process that never
    imports jax reports the two granted chips (not the four on the bus),
    and two one-chip actors hold different ones."""
    pci, dev = _fake_tpu_host(tmp_path, granted={1, 3})
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "RAY_TPU_NUM_CHIPS",
                        "TPU_VISIBLE_CHIPS")}
    out = subprocess.run(
        [sys.executable, "-c", _INIT_SNIPPET, _ROOT, pci, dev], env=env,
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["resources"]["TPU"] == 2
    assert got["resources"]["accelerator_type:TPU-V5-LITE"] == 1
    assert sorted(map(tuple, got["chips"])) == [(0,), (1,)]


@pytest.mark.parametrize("platforms,chips", [
    ("", 2), ("tpu,cpu", 2), ("cpu", 0)])
def test_probe_follows_jax_platform_pin(tmp_path, monkeypatch, platforms,
                                        chips):
    """Chips this process's JAX is pinned away from are not its
    resources. (conftest pins jax_platforms=cpu in code as well as in the
    variable, so both are moved here, and put back.)"""
    import jax
    pci, dev = _fake_tpu_host(tmp_path, granted={0, 2})
    monkeypatch.setattr(resource_spec, "_SYSFS_PCI_DEVICES", pci)
    monkeypatch.setattr(resource_spec, "_DEV", dev)
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    pinned = jax.config.jax_platforms
    jax.config.update("jax_platforms", platforms or None)
    try:
        count, kind = resource_spec.autodetect_num_tpus()
    finally:
        jax.config.update("jax_platforms", pinned)
    assert count == chips
    assert kind == ("TPU v5 lite" if chips else "")


def test_reserved_chips_map_to_their_own_devices(four_fake_chips):
    """get_tpu_devices(): a task's reserved chips are those jax devices,
    and a caller that reserved none sees them all."""
    import jax

    @ray_tpu.remote(num_tpus=1)
    class OneChip:
        def device(self):
            (device,) = ray_tpu.get_tpu_devices()
            return ray_tpu.get_tpu_ids(), device.id

    @ray_tpu.remote(num_tpus=2)
    def two_chips():
        return ray_tpu.get_tpu_ids(), [d.id for d in
                                       ray_tpu.get_tpu_devices()]

    actors = [OneChip.remote() for _ in range(2)]
    held = ray_tpu.get([a.device.remote() for a in actors])
    ids, device_ids = ray_tpu.get(two_chips.remote())
    local = jax.local_devices()
    for chip_ids, device_id in held:
        assert [local[i].id for i in chip_ids] == [device_id]
    assert [local[i].id for i in ids] == device_ids
    assert len({d for _, d in held} | set(device_ids)) == 4
    assert ray_tpu.get_tpu_devices() == local
