"""Model + parallel-layer tests on the virtual 8-device CPU mesh."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import exchange, gpt, lm
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


# -- what the model states about its activations (sharding.constrain) ----

def _loss_and_grads(cfg, mesh_cfg, n_devices, params, tokens, targets, mask,
                    rules=None, model=gpt):
    """``model.loss_fn``'s loss and gradients, traced under a mesh and its
    rules as a train step traces it."""
    from ray_tpu.parallel import mesh as mesh_mod, shard_tree
    mesh = build_mesh(mesh_cfg, devices=jax.devices()[:n_devices])
    rules = rules or ShardingRules()
    params = shard_tree(params, mesh, model.param_specs(cfg, rules))
    fn = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, cfg, tokens, targets, mask)[0]))
    previous = mesh_mod.current_mesh(), mesh_mod.current_rules()
    mesh_mod.set_current_mesh(mesh, rules)
    try:
        return jax.device_get(fn(params))
    finally:
        mesh_mod.set_current_mesh(*previous)


def _assert_fsdp_x_tp_matches_one_device(
        cfg, masked, mesh_cfg=MeshConfig(dp=1, fsdp=2, tp=2), rules=None,
        model=gpt, seq=32, rtol=1e-4, atol=1e-6):
    params = model.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, 256, (4, seq)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, 256, (4, seq)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (4, seq)), jnp.float32) \
        if masked else None
    one_loss, one_grads = _loss_and_grads(
        cfg, MeshConfig(dp=1, fsdp=1, tp=1), 1, params, tokens, targets, mask,
        model=model)
    loss, grads = _loss_and_grads(
        cfg, mesh_cfg, int(np.prod(mesh_cfg.shape())), params, tokens,
        targets, mask, rules, model)
    assert float(loss) == pytest.approx(float(one_loss), rel=1e-4)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads),
                                 jax.tree.leaves(one_grads)):
        np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("loss_chunk", [64, 100])  # 100 does not divide
@pytest.mark.parametrize("parallel_block", [True, False])
def test_fsdp_x_tp_matches_one_device(parallel_block, loss_chunk, masked):
    """The reordered sum of the parallel block, the chunks cut inside each
    data shard, the gathered head and the stated hidden states are the
    same numbers in another order: loss and every gradient leaf on
    fsdp=2 x tp=2 are the one-device values."""
    _assert_fsdp_x_tp_matches_one_device(
        gpt.config("gpt-tiny", parallel_block=parallel_block,
                   loss_chunk=loss_chunk), masked)


# -- the block's tp traffic as exchanges of slices of S --------------------

def _exchanges(cfg, mesh_cfg, seq, rules=None):
    """How many ``ppermute``s the traced loss holds on this mesh."""
    from ray_tpu.parallel import mesh as mesh_mod
    mesh = build_mesh(mesh_cfg,
                      devices=jax.devices()[:int(np.prod(mesh_cfg.shape()))])
    tokens = jnp.zeros((4, seq), jnp.int32)
    params = jax.eval_shape(lambda k: gpt.init(cfg, k), jax.random.PRNGKey(0))
    previous = mesh_mod.current_mesh(), mesh_mod.current_rules()
    mesh_mod.set_current_mesh(mesh, rules)
    try:
        return str(jax.make_jaxpr(lambda p: gpt.loss_fn(
            p, cfg, tokens, tokens)[0])(params)).count("ppermute")
    finally:
        mesh_mod.set_current_mesh(*previous)


@pytest.mark.parametrize("axis", [1, 2])
def test_place_slices_puts_every_slice_at_its_slot(axis):
    """``ops/place.py`` (interpreted here): slice t lands in block
    ``slots[t]`` of ``axis``, whatever the permutation, traced or not."""
    from ray_tpu.ops.place import place_slices
    rng = np.random.default_rng(11)
    slices = [jnp.asarray(rng.normal(size=(2, 8, 8, 4)), jnp.float32)
              for _ in range(3)]
    for slots in ([0, 1, 2], [2, 0, 1], [1, 2, 0]):
        got = jax.jit(partial(place_slices, axis=axis))(
            slices, jnp.asarray(slots, jnp.int32))
        want = jnp.concatenate(
            [slices[slots.index(block)] for block in range(3)], axis=axis)
        np.testing.assert_array_equal(got, want)
    # A tree a slice: one call, one result a leaf.
    pair = jax.jit(partial(place_slices, axis=axis))(
        [(a, 2 * a) for a in slices], jnp.asarray(slots, jnp.int32))
    np.testing.assert_array_equal(pair[0], want)
    np.testing.assert_array_equal(pair[1], 2 * want)


@pytest.mark.parametrize("tp", [2, 4])
def test_ring_products_match_the_plain_products(tp):
    """``exchange.gathered_product`` / ``ring_place`` / ``ring_split`` /
    ``scattered_product`` over a ring of tp chips against the products they
    stand for, ``(x @ w1) @ w2`` with w1 split by columns and w2 by rows,
    and against its gradients: every chip takes its slice of S in and
    gives its slice of the sum back."""
    from jax.sharding import Mesh, PartitionSpec as P

    from ray_tpu._private.jax_compat import shard_map
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
    rng = np.random.default_rng(7)
    x, w1, w2, cot = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                      for shape in ((2, 8 * tp, 16), (16, 4 * tp),
                                    (4 * tp, 16), (2, 8 * tp, 16)))

    def on_slices(x, w1, w2):
        parts = exchange.gathered_product(x, [lambda rows: rows,
                                              lambda rows: rows @ w1])
        whole = exchange.ring_place([rows for rows, _ in parts])  # x again
        again = exchange.ring_split(whole)
        return whole, exchange.scattered_product(
            lambda inputs, w: (inputs[0] + 0 * inputs[1] @ w[0]) @ w[1],
            [(part[1], rows) for part, rows in zip(parts, again)], (w1, w2),
            after=parts[-1][1])

    ringed = shard_map(on_slices, mesh=mesh,
                       in_specs=(P(None, "tp"), P(None, "tp"), P("tp")),
                       out_specs=(P(), P(None, "tp")), check_vma=False)

    def plain(x, w1, w2):
        return x, (x @ w1) @ w2

    def loss(fn, *args):
        whole, out = fn(*args)
        return (out * cot).sum() + (whole * cot).sum()

    with jax.default_matmul_precision("highest"):
        for got, want in zip(jax.jit(ringed)(x, w1, w2), plain(x, w1, w2)):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        grads = jax.jit(jax.grad(lambda *a: loss(ringed, *a),
                                 argnums=(0, 1, 2)))(x, w1, w2)
        wanted = jax.grad(lambda *a: loss(plain, *a),
                          argnums=(0, 1, 2))(x, w1, w2)
    for got, want in zip(grads, wanted):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("parallel_block", [True, False])
def test_block_on_slices_matches_one_device(parallel_block, tp):
    """The flash path on a mesh with tp: the stream split over tp along S,
    the block per shard of tp, its sum and gather as exchanges (a parallel
    block: two ``ppermute``s a layer at tp = 2; a sequential one: four).
    Loss and every gradient leaf are the one-device values, remat on."""
    cfg = gpt.config("gpt-tiny", attn_impl="flash", remat=True,
                     parallel_block=parallel_block, loss_chunk=64)
    mesh_cfg = MeshConfig(dp=1, fsdp=2, tp=tp)
    assert _exchanges(cfg, mesh_cfg, 32) >= (2 if parallel_block else 4)
    _assert_fsdp_x_tp_matches_one_device(cfg, masked=True, mesh_cfg=mesh_cfg)


@pytest.mark.parametrize("why,cfg,mesh_cfg,rules,seq", [
    ("dot attention", gpt.config("gpt-tiny"),
     MeshConfig(dp=1, fsdp=2, tp=2), None, 32),
    ("tp does not divide S", gpt.config("gpt-tiny", attn_impl="flash"),
     MeshConfig(dp=1, fsdp=2, tp=4), None, 30),
    ("S lies over sp", gpt.config("gpt-tiny", attn_impl="ring"),
     MeshConfig(dp=1, fsdp=1, tp=2, sp=2), ShardingRules(sequence="sp"), 32),
    ("tp splits no weight", gpt.config("gpt-tiny", attn_impl="flash"),
     MeshConfig(dp=1, fsdp=2, tp=2), dp_rules(), 32),
    ("no tp", gpt.config("gpt-tiny", attn_impl="flash"),
     MeshConfig(dp=2, fsdp=2, tp=1), None, 32)],
    ids=lambda value: value.replace(" ", "_")
    if isinstance(value, str) else None)
def test_block_keeps_the_partitioners_path(why, cfg, mesh_cfg, rules, seq):
    """Where the mesh, the rules or the shape do not allow the exchange the
    block is the partitioner's as before: no ``ppermute`` beyond ring
    attention's own, and the one-device numbers."""
    ring = 2 if cfg.attn_impl == "ring" else 0  # K and V, in the one scan body
    assert _exchanges(cfg, mesh_cfg, seq, rules) == ring, why
    _assert_fsdp_x_tp_matches_one_device(cfg, masked=False, mesh_cfg=mesh_cfg,
                                         rules=rules, seq=seq)


def test_another_family_is_right_on_a_mesh_with_tp():
    """``lm.embed`` and ``lm.scan_blocks`` are every model's: a family
    whose block says nothing of slices keeps its stream whole along S on a
    mesh with tp, and its one-device numbers."""
    from ray_tpu.models import granite
    cfg = granite.config("granite-tiny", attn_impl="flash")
    _assert_fsdp_x_tp_matches_one_device(cfg, masked=False, model=granite,
                                         seq=64, rtol=2e-3, atol=1e-5)


# -- the tied head: wte is the lookup's table and the head's weight --------

TIED = gpt.config("gpt-tiny", tie_embeddings=True)


def test_tied_param_specs_match_init():
    params = jax.eval_shape(lambda k: gpt.init(TIED, k),
                            jax.random.PRNGKey(0))
    assert "lm_head" not in params and "lm_head_bias" not in params
    specs = gpt.param_specs(TIED, ShardingRules())
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    assert sum(a.size for a in jax.tree.leaves(params)) == TIED.num_params()


def test_tied_chunked_loss_equals_unchunked():
    params = gpt.init(TIED, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, 256, (4, 32)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, 256, (4, 32)), jnp.int32)

    def loss_and_grads(chunk):
        cfg = gpt.config("gpt-tiny", tie_embeddings=True, loss_chunk=chunk)
        return jax.value_and_grad(
            lambda p: gpt.loss_fn(p, cfg, tokens, targets)[0])(params)

    want, want_g = loss_and_grads(0)
    got, got_g = loss_and_grads(64)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    # wte's gradient sums the lookup's and the head's, chunk by chunk.
    np.testing.assert_allclose(got_g["wte"], want_g["wte"], rtol=1e-4,
                               atol=1e-6)


def test_tied_head_on_fsdp_x_tp_matches_one_device():
    """``lm.head_gathered(tied=True)``: wte gathered along d for the chunk
    loop, split over the vocabulary for the head and by fsdp for the
    lookup, its two gradients summed."""
    _assert_fsdp_x_tp_matches_one_device(
        gpt.config("gpt-tiny", tie_embeddings=True, loss_chunk=64),
        masked=True)


@pytest.mark.parametrize("chunk", [0, 3, 64, 100, 128, 4096])
def test_chunked_ce_equals_the_unchunked_loss(chunk):
    """Whatever the chunk: none, fewer tokens than rows (one position a
    slice), a divisor, a non-divisor, one slice, more than there is."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(4, 32, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 50)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 50, (4, 32)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (4, 32)), jnp.float32)

    def sums(x, w, chunk):
        return lm.chunked_ce(lambda h: h @ w, x, targets, mask, chunk,
                              z_loss=1e-4)

    want = lm.ce_stats(x @ w, targets, mask, 1e-4)
    np.testing.assert_allclose(sums(x, w, chunk), want, rtol=1e-5)
    got_g = jax.grad(lambda x, w: sums(x, w, chunk)[0], (0, 1))(x, w)
    want_g = jax.grad(lambda x, w: sums(x, w, 0)[0], (0, 1))(x, w)
    for got, wanted in zip(got_g, want_g):
        np.testing.assert_allclose(got, wanted, rtol=1e-4, atol=1e-5)


def test_constrain_is_nothing_without_a_mesh_or_on_one_device():
    from ray_tpu.parallel import mesh as mesh_mod
    from ray_tpu.parallel.sharding import ambient_spec, constrain
    x = jnp.ones((4, 8, 2))
    assert constrain(x, "batch", "sequence", None) is x
    one = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                     devices=jax.devices()[:1])
    four = build_mesh(MeshConfig(dp=1, fsdp=2, tp=1, sp=2),
                      devices=jax.devices()[:4])
    previous = mesh_mod.current_mesh(), mesh_mod.current_rules()
    try:
        mesh_mod.set_current_mesh(one)
        assert constrain(x, "batch", "sequence", None) is x
        # The rules registered beside the mesh decide; none: the defaults.
        mesh_mod.set_current_mesh(four)
        assert ambient_spec(four, "batch", "sequence", None) == \
            jax.sharding.PartitionSpec(("dp", "fsdp"), None, None)
        mesh_mod.set_current_mesh(four, ShardingRules(sequence="sp"))
        y = jax.jit(lambda a: constrain(a, "batch", "sequence", None))(x)
        assert y.sharding.is_equivalent_to(jax.sharding.NamedSharding(
            four, jax.sharding.PartitionSpec(("dp", "fsdp"), "sp", None)),
            x.ndim)
        # A mesh built by hand may lack axes the rules name.
        bare = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("fsdp",))
        assert ambient_spec(bare, "batch", "heads") == \
            jax.sharding.PartitionSpec(("fsdp",), None)
    finally:
        mesh_mod.set_current_mesh(*previous)


# What the TPU compiler prints, cut to what census reads: a synchronous
# all-reduce in a loop's body, an asynchronous all-gather whose start,
# carry and finish are three fusions repeating it under one channel_id, a
# variadic all-to-all in ENTRY, and the -start/-done form of other backends.
CENSUS_HLO = """\
HloModule jit_step

%add.1 (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%x, %y)
}

%fused_start (p: bf16[2048,256]) -> (bf16[2048,256], bf16[4096,256]) {
  %p = bf16[2048,256]{1,0:T(8,128)(2,1)} parameter(0)
  %all-gather.1 = bf16[4096,256]{1,0:T(8,128)(2,1)} all-gather(%p), channel_id=7, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(step)/jvp()/while/body/block/attention/dot_general"}
  ROOT %custom-call.1 = (bf16[2048,256]{1,0}, bf16[4096,256]{1,0}) custom-call(%all-gather.1), custom_call_target="AsyncCollectiveStart"
}

%fused_matmul (x.1: bf16[8,128,256], y.1: bf16[256,256]) -> bf16[8,128,256] {
  %x.1 = bf16[8,128,256]{2,1,0} parameter(0)
  %y.1 = bf16[256,256]{1,0} parameter(1)
  ROOT %convolution.1 = bf16[8,128,256]{2,1,0} convolution(%x.1, %y.1), dim_labels=0bf_io0->0bf
}

%fused_done (p.1: bf16[2048,256]) -> bf16[4096,256] {
  %p.1 = bf16[2048,256]{1,0} parameter(0)
  %all-gather.2 = bf16[4096,256]{1,0} all-gather(%p.1), channel_id=7, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}, use_global_device_ids=true
  ROOT %custom-call.2 = bf16[4096,256]{1,0} custom-call(%all-gather.2), custom_call_target="AsyncCollectiveDone"
}

%body (carry: (s32[], bf16[8,128,256])) -> (s32[], bf16[8,128,256]) {
  %carry = (s32[], bf16[8,128,256]{2,1,0}) parameter(0)
  %i = s32[] get-tuple-element(%carry), index=0
  %h = bf16[8,128,256]{2,1,0} get-tuple-element(%carry), index=1
  %w = bf16[2048,256]{1,0} constant(0)
  %start = (bf16[2048,256]{1,0}, bf16[4096,256]{1,0}) fusion(%w), kind=kCustom, calls=%fused_start
  %all-reduce.3 = bf16[8,128,256]{2,1,0:T(8,128)(2,1)} all-reduce(%h), channel_id=9, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%add.1, metadata={op_name="jit(step)/jvp()/while/body/block/mlp/dot_general"}
  %done = bf16[4096,256]{1,0} fusion(%w), kind=kCustom, calls=%fused_done
  ROOT %tuple.1 = (s32[], bf16[8,128,256]{2,1,0}) tuple(%i, %all-reduce.3)
}

%cond (carry.1: (s32[], bf16[8,128,256])) -> pred[] {
  %carry.1 = (s32[], bf16[8,128,256]{2,1,0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: bf16[8,128,256], b: f32[8,128,256]) -> bf16[8,128,256] {
  %a = bf16[8,128,256]{2,1,0} parameter(0)
  %b = f32[8,128,256]{2,1,0} parameter(1)
  %zero = s32[] constant(0)
  %init = (s32[], bf16[8,128,256]{2,1,0}) tuple(%zero, %a)
  %while.1 = (s32[], bf16[8,128,256]{2,1,0}) while(%init), condition=%cond, body=%body
  %all-to-all.4 = (f32[4,128,256]{2,1,0}, /*index=1*/f32[4,128,256]{2,1,0}) all-to-all(%b, %b), channel_id=11, replica_groups={{0,2},{1,3}}
  %all-gather-start.5 = (bf16[8,128,256]{2,1,0}, bf16[32,128,256]{2,1,0}) all-gather-start(%a), channel_id=12, replica_groups=[1,4]<=[4], dimensions={0}
  %y = bf16[256,256]{1,0} constant(0)
  %matmul.1 = bf16[8,128,256]{2,1,0} fusion(%a, %y), kind=kOutput, calls=%fused_matmul
  %scaled = bf16[8,128,256]{2,1,0} multiply(%matmul.1, %matmul.1)
  %all-gather-done.5 = bf16[32,128,256]{2,1,0} all-gather-done(%all-gather-start.5)
  %collective-permute.6 = bf16[8,128,256]{2,1,0} collective-permute(%a), channel_id=13, source_target_pairs={{0,1},{1,0}}
  %collective-permute-start.7 = (bf16[8,128,256]{2,1,0}, bf16[8,128,256]{2,1,0}) collective-permute-start(%a), channel_id=1, source_target_pairs={{0,1},{1,0}}
  %collective-permute-done.7 = bf16[8,128,256]{2,1,0} collective-permute-done(%collective-permute-start.7)
  %collective-permute-start.8 = (bf16[8,128,256]{2,1,0}, bf16[8,128,256]{2,1,0}) collective-permute-start(%scaled), channel_id=1, source_target_pairs={{0,1},{1,0}}
  %collective-permute-done.8 = bf16[8,128,256]{2,1,0} collective-permute-done(%collective-permute-start.8)
  ROOT %out = bf16[8,128,256]{2,1,0} get-tuple-element(%while.1), index=1
}
"""


def test_census_reads_hlo_text():
    from ray_tpu.parallel.collectives import census
    ops = {op["name"]: op for op in census(CENSUS_HLO)}
    # One per collective: the fusions' repeat and the -done are not others.
    assert sorted(ops) == ["all-gather-start.5", "all-gather.1",
                           "all-reduce.3", "all-to-all.4",
                           "collective-permute-start.7",
                           "collective-permute-start.8",
                           "collective-permute.6"]
    inner = ops["all-gather.1"]  # inside a fusion the loop's body calls
    assert (inner["kind"], inner["computation"], inner["in_loop"]) == \
        ("all-gather", "body", True)
    assert inner["bytes"] == 4096 * 256 * 2 and inner["group_size"] == 2
    assert inner["op_name"].endswith("attention/dot_general")
    reduced = ops["all-reduce.3"]
    assert reduced["arrays"] == [("bf16", (8, 128, 256))]
    assert reduced["in_loop"] and reduced["group_size"] == 2
    exchanged = ops["all-to-all.4"]  # a tuple: its arrays summed
    assert exchanged["bytes"] == 2 * 4 * 128 * 256 * 4
    assert [dtype for dtype, _ in exchanged["arrays"]] == ["f32", "f32"]
    assert (exchanged["computation"], exchanged["in_loop"]) == \
        ("main", False)
    started = ops["all-gather-start.5"]  # the result's half of the pair
    assert (started["kind"], started["bytes"], started["group_size"]) == \
        ("all-gather", 32 * 128 * 256 * 2, 4)
    assert ops["collective-permute.6"]["group_size"] == 2
    assert ops["collective-permute.6"]["op_name"] == ""
    # Which are a start and a done, and what the schedule puts between:
    # the fused form from its first fusion to its last, a -start to its
    # -done, bookkeeping left out, matmuls counted; two ppermutes of one
    # shard_map share a channel_id and are two.
    assert {name: op["is_async"] for name, op in ops.items()} == {
        "all-gather.1": True, "all-gather-start.5": True,
        "collective-permute-start.7": True,
        "collective-permute-start.8": True, "all-reduce.3": False,
        "all-to-all.4": False, "collective-permute.6": False}
    assert (inner["between"], inner["matmuls_between"]) == (
        ["all-reduce.3"], 0)
    assert (started["between"], started["matmuls_between"]) == (
        ["matmul.1", "scaled"], 1)
    assert ops["collective-permute-start.7"]["between"] == []
    assert reduced["between"] == []
