"""Model + parallel-layer tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt
from ray_tpu.parallel import (MeshConfig, ShardingRules, build_mesh, dp_rules,
                              tp_fsdp_rules)
from ray_tpu.parallel.train_step import (default_optimizer, init_train_state,
                                         make_train_step)


def test_mesh_config_resolve():
    cfg = MeshConfig(dp=2, fsdp=-1, tp=2).resolve(8)
    assert cfg.fsdp == 2
    assert cfg.shape() == (2, 2, 2, 1, 1, 1)
    with pytest.raises(ValueError):
        MeshConfig(dp=3, fsdp=1, tp=1).resolve(8)
    with pytest.raises(ValueError):
        MeshConfig(dp=-1, fsdp=-1).resolve(8)


def test_build_mesh_axes():
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    assert mesh.axis_names == ("dp", "fsdp", "tp", "sp", "ep", "pp")
    assert dict(mesh.shape)["tp"] == 2


def test_sharding_rules_spec():
    rules = tp_fsdp_rules()
    spec = rules.spec("layers", "embed", "heads", None)
    assert spec == jax.sharding.PartitionSpec(None, "fsdp", "tp", None)
    assert dp_rules().spec("embed") == jax.sharding.PartitionSpec(None)


def test_gpt_forward_shape():
    cfg = gpt.config("gpt-tiny")
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = gpt.forward(params, cfg, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)


def test_gpt_causality():
    """Future tokens must not influence earlier logits."""
    cfg = gpt.config("gpt-tiny")
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (1, 16))
    a = np.asarray(gpt.forward(params, cfg, jnp.asarray(toks, jnp.int32)))
    toks2 = toks.copy()
    toks2[0, -1] = (toks2[0, -1] + 1) % cfg.vocab_size  # change last token
    b = np.asarray(gpt.forward(params, cfg, jnp.asarray(toks2, jnp.int32)))
    np.testing.assert_allclose(a[0, :-1], b[0, :-1], rtol=2e-4, atol=2e-4)
    assert not np.allclose(a[0, -1], b[0, -1])


def test_gpt_param_count_matches_init():
    cfg = gpt.config("gpt-tiny")
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert actual == cfg.num_params()


def test_train_step_loss_decreases():
    cfg = gpt.config("gpt-tiny")
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    rules = tp_fsdp_rules()
    opt = default_optimizer(learning_rate=1e-3, warmup_steps=1)
    state = init_train_state(cfg, mesh, rules, opt, seed=0)
    step = make_train_step(cfg, mesh, rules, opt)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, 256, (8, 32)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, 256, (8, 32)), jnp.int32),
    }
    state, m0 = step(state, batch)
    for _ in range(10):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])
    assert int(state["step"]) == 11


@pytest.mark.parametrize("mesh_cfg,n_devices", [
    (MeshConfig(dp=1, fsdp=1, tp=1), 1), (MeshConfig(dp=1, fsdp=2, tp=2), 4)])
def test_train_step_compiles_once(mesh_cfg, n_devices):
    """The state a step returns has the layout of the state it took, so
    the second call is the first call's program. (It used to be a second
    compile: init's state and the step's output disagreed on shardings,
    11-14 s per cold start of gpt-1.3b on the chip.) Adafactor: its
    factored moments were what the compiler laid out differently."""
    from ray_tpu.parallel.train_step import memory_efficient_optimizer
    cfg = gpt.config("gpt-tiny")
    mesh = build_mesh(mesh_cfg, devices=jax.devices()[:n_devices])
    opt = memory_efficient_optimizer(warmup_steps=1)
    state = init_train_state(cfg, mesh, ShardingRules(), opt, seed=0)
    step = make_train_step(cfg, mesh, ShardingRules(), opt)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, 256, (8, 32)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, 256, (8, 32)), jnp.int32),
    }
    state, _ = step(state, batch)
    compiles = []

    def on_compile(event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration_secs)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        for _ in range(2):
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert compiles == []


def test_sharding_strategies_agree():
    """DP-only and TP+FSDP must compute the same loss (GSPMD correctness)."""
    cfg = gpt.config("gpt-tiny")
    rng = np.random.default_rng(1)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, 256, (4, 32)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, 256, (4, 32)), jnp.int32),
    }
    losses = []
    for mesh_cfg, rules in [
        (MeshConfig(dp=8, fsdp=1, tp=1), dp_rules()),
        (MeshConfig(dp=1, fsdp=2, tp=4), tp_fsdp_rules()),
        (MeshConfig(dp=2, fsdp=2, tp=1, sp=2),
         ShardingRules(sequence="sp")),
    ]:
        mesh = build_mesh(mesh_cfg)
        opt = default_optimizer(learning_rate=1e-3, warmup_steps=1)
        state = init_train_state(cfg, mesh, rules, opt, seed=0)
        step = make_train_step(cfg, mesh, rules, opt)
        _, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[0] == pytest.approx(losses[1], rel=1e-4)
    assert losses[0] == pytest.approx(losses[2], rel=1e-4)


def test_grad_accumulation_matches_full_batch():
    cfg = gpt.config("gpt-tiny")
    mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                      devices=jax.devices()[:1])
    rules = dp_rules()
    rng = np.random.default_rng(2)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, 256, (8, 16)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, 256, (8, 16)), jnp.int32),
    }
    opt = default_optimizer(learning_rate=1e-3, warmup_steps=1)
    s1 = init_train_state(cfg, mesh, rules, opt, seed=0)
    s2 = init_train_state(cfg, mesh, rules, opt, seed=0)
    full = make_train_step(cfg, mesh, rules, opt)
    accum = make_train_step(cfg, mesh, rules, opt, accum_steps=4)
    s1, m1 = full(s1, batch)
    s2, m2 = accum(s2, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)


def test_graft_entry_contract():
    import __graft_entry__ as graft
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert out.ndim == 3
    graft.dryrun_multichip(8)


def test_multi_slice_mesh_layout_and_validation():
    """MeshConfig(slices=N) builds a hybrid DCN x ICI mesh: the dp
    axis's outer positions enumerate slices (only gradient psums cross
    the slice boundary); dp must divide by slices."""
    import numpy as np
    import pytest as _pytest

    import jax
    from ray_tpu.parallel import MeshConfig, build_mesh

    devices = jax.devices()[:8]
    mesh = build_mesh(MeshConfig(slices=2, dp=2, fsdp=2, tp=-1),
                      devices=devices)
    assert dict(mesh.shape) == {"dp": 2, "fsdp": 2, "tp": 2, "sp": 1,
                                "ep": 1, "pp": 1}
    grid = np.asarray(mesh.devices)
    first, second = set(devices[:4]), set(devices[4:])
    assert set(grid[0].ravel().tolist()) <= first
    assert set(grid[1].ravel().tolist()) <= second

    with _pytest.raises(ValueError, match="multiple of slices"):
        build_mesh(MeshConfig(slices=2, dp=1, fsdp=-1), devices=devices)
    with _pytest.raises(ValueError):
        build_mesh(MeshConfig(slices=3, dp=3, fsdp=-1), devices=devices)


def test_multi_slice_mesh_runs_train_step():
    """One training step compiles and runs over the 2-slice mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshConfig, ShardingRules, build_mesh
    from ray_tpu.parallel.train_step import (default_optimizer,
                                             init_train_state,
                                             make_train_step)

    mesh = build_mesh(MeshConfig(slices=2, dp=2, fsdp=2, tp=-1),
                      devices=jax.devices()[:8])
    cfg = gpt.config("gpt-tiny")
    opt = default_optimizer(learning_rate=1e-3)
    state = init_train_state(cfg, mesh, ShardingRules(), opt, seed=0)
    step = make_train_step(cfg, mesh, ShardingRules(), opt)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)),
                              jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)),
                               jnp.int32),
    }
    _, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
