"""``models/lm.py``'s ``Decoder`` on a toy family defined here: two kinds of
layer, one with aux. What every family gets from the shell and none tests
for itself: the runs' names and depths, the parameter and spec trees from
one table, the aux merged over runs of which only some return any, the head
and loss's variants, and the refusal of an ``ep`` mesh."""

from dataclasses import dataclass, replace
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import lm
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.mesh import set_current_mesh
from ray_tpu.parallel.sharding import ShardingRules


@dataclass(frozen=True)
class ToyConfig:
    vocab_size: int = 32
    hidden_size: int = 8
    layers: Tuple[str, ...] = ("plain", "counted", "counted", "plain",
                               "counted")
    #: Every layer returns ``floor`` too (as Kimi's ``decay_floor``).
    floors: bool = False
    rms_norm_eps: float = 1e-5
    multiplier: float = 3.0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "full"
    loss_chunk: int = 0


def _marks(key, shape):
    """Every layer of a stack its own number, in each of three slots."""
    return jnp.broadcast_to(
        jnp.arange(1.0, shape[0] + 1)[:, None], shape)


def _shapes(cfg):
    d = cfg.hidden_size
    plain = {"scale": ((d,), ("embed",), lm.ones),
             "w": ((d, d), ("embed", "mlp"), 0.5)}
    return {"plain": plain,
            "counted": dict(plain, bias=((d,), (None,), lm.zeros),
                            marks=((3,), (None,), _marks))}


def _block(cfg, kind, h, layer, positions):
    h = h + lm.rmsnorm(h, layer["scale"], cfg.rms_norm_eps) @ layer["w"]
    aux = {"floor": -layer["scale"].sum()} if cfg.floors else {}
    if kind == "counted":
        aux["sizes"] = layer["marks"].astype(jnp.int32)
        h = h + layer["bias"]
    return h, aux or None


def toy(**kw):
    return lm.Decoder(name="toy", shapes=_shapes, block=_block, **kw)


CFG = ToyConfig()
LAYERINGS = [("plain",), ("counted",), CFG.layers,
             ("counted", "counted", "plain"),
             ("plain", "plain", "counted", "plain", "plain", "plain")]


def _tokens(cfg, batch=2, seq=6):
    return jax.random.randint(jax.random.PRNGKey(7), (batch, seq), 0,
                              cfg.vocab_size)


@pytest.mark.parametrize("layers", LAYERINGS, ids=lambda l: "-".join(l))
def test_runs_are_named_and_as_deep_as_the_layers_say(layers):
    cfg = replace(CFG, layers=layers)
    runs = lm.runs(cfg.layers)
    assert [name for name, _, _ in runs] == [
        f"run{i:02d}_{kind}" for i, (_, kind, _) in enumerate(runs)]
    assert [kind for _, kind, n in runs for _ in range(n)] == list(layers)
    assert all(a[1] != b[1] for a, b in zip(runs, runs[1:]))
    params = toy().init(cfg, jax.random.PRNGKey(0))
    assert set(params) == {"wte", "lnf_scale", "lm_head"} | {
        name for name, _, _ in runs}
    for name, kind, depth in runs:
        assert set(params[name]) == set(_shapes(cfg)[kind])
        assert {leaf.shape[0] for leaf in params[name].values()} == {depth}


@pytest.mark.parametrize("tied", [False, True])
def test_parameters_and_specs_come_from_one_table(tied):
    shell = toy(tied=tied, final_norm="out_norm_scale")
    params = shell.init(CFG, jax.random.PRNGKey(3))
    specs = shell.param_specs(CFG, ShardingRules())
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    assert ("lm_head" in params) != tied
    for run, kind, depth in lm.runs(CFG.layers):
        for name, (shape, axes, _) in _shapes(CFG)[kind].items():
            assert params[run][name].shape == (depth,) + shape
            assert len(specs[run][name]) == 1 + len(axes)
    # The draws: a std, ones, zeros and a family's own callable; the key
    # split as ``init`` says (the stacks take the last of the split).
    keys = jax.random.split(jax.random.PRNGKey(3), 2 if tied else 3)
    np.testing.assert_array_equal(params["wte"], 0.02 * jax.random.normal(
        keys[0], (CFG.vocab_size, CFG.hidden_size)))
    run, _, depth = lm.runs(CFG.layers)[1]
    k_scale, k_w, k_bias, k_marks = jax.random.split(
        jax.random.fold_in(keys[-1], 1), 4)
    np.testing.assert_array_equal(params[run]["w"], 0.5 * jax.random.normal(
        k_w, (depth, CFG.hidden_size, CFG.hidden_size)))
    assert np.all(np.asarray(params[run]["scale"]) == 1)
    assert np.all(np.asarray(params[run]["bias"]) == 0)
    np.testing.assert_array_equal(params[run]["marks"][:, 0], [1.0, 2.0])
    assert np.all(np.asarray(params["out_norm_scale"]) == 1)


def _numbered(params, cfg):
    """Every counted layer's marks set to its place in the model."""
    place, out = 0, dict(params)
    for run, kind, depth in lm.runs(cfg.layers):
        if kind == "counted":
            out[run] = dict(params[run], marks=jnp.broadcast_to(
                (place + jnp.arange(depth, dtype=jnp.float32))[:, None],
                (depth, 3)))
        place += depth
    return out


@pytest.mark.parametrize("floors", [False, True], ids=["some", "every"])
@pytest.mark.parametrize("layers", LAYERINGS, ids=lambda l: "-".join(l))
def test_aux_is_merged_over_the_runs_that_return_it(layers, floors):
    """``some``: runs without aux beside runs with (afmoe with its leading
    dense run). ``every``: every run returns ``floor`` and some ``sizes``
    (Kimi's ``decay_floor`` beside the expert layers' aux)."""
    cfg = replace(CFG, layers=layers, floors=floors)
    shell = toy()
    params = _numbered(shell.init(cfg, jax.random.PRNGKey(0)), cfg)
    _, aux = jax.jit(lambda p, t: shell.hidden_states(p, cfg, t))(
        params, _tokens(cfg))
    counted = [i for i, kind in enumerate(layers) if kind == "counted"]
    assert set(aux) == ({"floor"} if floors else set()) | (
        {"sizes"} if counted else set())
    if counted:
        np.testing.assert_array_equal(aux["sizes"][:, 0], counted)
        assert aux["sizes"].shape == (len(counted), 3)
    if floors:
        assert aux["floor"].shape == (len(layers),)


def test_merged_aux_is_the_sorted_union_each_name_in_layer_order():
    one, two = jnp.ones((1, 2)), jnp.zeros((2, 2))
    assert lm.merged_aux([None, None]) == {}
    merged = lm.merged_aux([{"b": one}, None, {"a": two, "b": two},
                            {"a": one}])
    assert list(merged) == ["a", "b"]
    np.testing.assert_array_equal(merged["a"], jnp.concatenate([two, one]))
    np.testing.assert_array_equal(merged["b"], jnp.concatenate([one, two]))


def _by_hand(params, cfg, tokens, scale=None):
    x = params["wte"][tokens]
    if scale is not None:
        x = x * scale
    for run, kind, depth in lm.runs(cfg.layers):
        for i in range(depth):
            x, _ = _block(cfg, kind, x, jax.tree.map(
                lambda leaf: leaf[i], params[run]), None)
    return lm.rmsnorm(x, params["lnf_scale"], cfg.rms_norm_eps)


@pytest.mark.parametrize("variant", ["plain", "tied", "multiplier",
                                     "divisor", "remat"])
def test_the_forward_pass_is_the_blocks_in_order_and_the_head(variant):
    cfg = replace(CFG, remat=variant == "remat")
    shell = toy(
        tied=variant == "tied",
        embed_scale=(lambda cfg: cfg.multiplier)
        if variant == "multiplier" else None,
        logits_divisor=(lambda cfg: cfg.multiplier)
        if variant == "divisor" else None)
    params = shell.init(cfg, jax.random.PRNGKey(1))
    tokens = _tokens(cfg)
    hidden = _by_hand(params, cfg, tokens,
                      cfg.multiplier if variant == "multiplier" else None)
    if variant == "divisor":
        hidden = hidden / cfg.multiplier
    want = hidden @ (params["wte"].T if variant == "tied"
                     else params["lm_head"])
    logits, aux = shell.forward_with_aux(params, cfg, tokens)
    np.testing.assert_allclose(logits, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(logits, shell.forward(params, cfg, tokens))
    assert set(aux) == {"sizes"}


@pytest.mark.parametrize("leading", [0, 2])
def test_a_family_names_its_stacks_and_one_may_hold_no_layer(leading):
    """DeepSeek's two stacks under their own names; with no leading dense
    layer the first is in the tree, with no layers, and not in the scan."""
    shell = toy(runs_of=lambda cfg: (("first", "plain", leading),
                                     ("rest", "counted", 3)))
    params = shell.init(CFG, jax.random.PRNGKey(2))
    assert params["first"]["w"].shape[0] == leading
    assert set(shell.param_specs(CFG, ShardingRules())["first"]) == {
        "scale", "w"}
    cfg = replace(CFG, layers=("plain",) * leading + ("counted",) * 3)
    by_kind = dict(params, **{run: params[stack] for (run, _, _), stack in zip(
        lm.runs(cfg.layers), ("first", "rest") if leading else ("rest",))})
    hidden, aux = shell.hidden_states(params, CFG, _tokens(CFG))
    np.testing.assert_allclose(
        hidden, _by_hand(by_kind, cfg, _tokens(CFG)), rtol=1e-5, atol=1e-5)
    assert aux["sizes"].shape == (3, 3)


@pytest.mark.parametrize("loss_chunk", [0, 4])
def test_the_loss_carries_the_familys_metrics(loss_chunk):
    cfg = replace(CFG, loss_chunk=loss_chunk)
    shell = toy(metrics=lambda cfg, aux, targets: {
        "toy_rows": aux["sizes"].sum() + targets.size})
    params = _numbered(shell.init(cfg, jax.random.PRNGKey(2)), cfg)
    tokens = _tokens(cfg)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones(tokens.shape).at[:, -1].set(0)
    loss, metrics = shell.loss_fn(params, cfg, tokens, targets, mask)
    x, aux = shell.hidden_states(params, cfg, tokens)
    again, _ = shell.loss_of_hidden(params, cfg, x, aux, targets, mask)
    logp = jax.nn.log_softmax(shell.forward(params, cfg, tokens))
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    np.testing.assert_allclose(loss, (nll * mask).sum() / mask.sum(),
                               rtol=1e-5)
    np.testing.assert_allclose(loss, again, rtol=1e-6)
    assert set(metrics) == {"loss", "accuracy", "perplexity", "toy_rows"}
    assert int(metrics["toy_rows"]) == 3 * (1 + 2 + 4) + tokens.size
    assert set(toy().loss_fn(params, cfg, tokens, targets)[1]) == {
        "loss", "accuracy", "perplexity"}


def _heads():
    """{name: (head(params, x), params)}: the heads the families run. The
    shell's tied and untied (divided or not) and GPT-J's, a matrix with a
    bias."""
    from ray_tpu.models import gpt
    key = jax.random.PRNGKey(5)
    heads = {}
    for name, kw in {"tied": dict(tied=True), "untied": {},
                     "tied_divided": dict(
                         tied=True, logits_divisor=lambda cfg: 8.0),
                     "untied_divided": dict(
                         logits_divisor=lambda cfg: 8.0)}.items():
        shell = toy(**kw)
        top = shell.init(replace(CFG, layers=()), key)
        heads[name] = (lambda p, x, shell=shell: shell.head(p, CFG, x),
                       {leaf: top[leaf] for leaf in ("wte", "lm_head")
                        if leaf in top and (leaf == "wte") == shell.tied})
    cfg = gpt.config("gpt-tiny", d_model=CFG.hidden_size,
                     vocab_size=CFG.vocab_size, dtype=jnp.float32)
    w, b = jax.random.normal(key, (2, CFG.hidden_size, CFG.vocab_size))
    heads["untied_bias"] = (lambda p, x: gpt._head(p, cfg, x),
                            {"lm_head": w, "lm_head_bias": b[0]})
    return heads


HEADS = ("tied", "untied", "tied_divided", "untied_divided", "untied_bias")


@pytest.mark.parametrize("masked,upstream", [(False, 1.0), (True, 3.0)])
@pytest.mark.parametrize("chunk", [4, 7, 16])  # 7 does not divide S = 10
@pytest.mark.parametrize("z_loss", [0.0, 1e-2])
@pytest.mark.parametrize("head", HEADS)
def test_the_chunked_loss_is_plain_autodiff_of_the_whole_one(
        head, z_loss, chunk, masked, upstream):
    """``lm.chunked_ce``'s rule (cotangents formed in the forward walk,
    scaled in the backward pass) against ``jax.grad`` of ``ce_stats`` over
    the whole logits: loss, metrics and every gradient leaf, under a mask
    with zeros, a chunk that does not divide, and a cotangent that is not
    1; and the primal alone, with no gradient asked."""
    head, params = _heads()[head]
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(3, 10, CFG.hidden_size)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, CFG.vocab_size, (3, 10)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (3, 10)), jnp.float32) \
        if masked else None

    def chunked(params, x):
        loss, metrics = lm.next_token_loss(
            lambda h: head(params, h), x, targets, mask, chunk, z_loss)
        return upstream * loss, metrics

    def whole(params, x):
        mask32 = jnp.ones(targets.shape) if mask is None else mask
        nll_sum, hit_sum = lm.ce_stats(head(params, x), targets, mask32,
                                       z_loss)
        loss = nll_sum / mask32.sum()
        return upstream * loss, {
            "loss": loss, "accuracy": hit_sum / mask32.sum(),
            "perplexity": jnp.exp(loss)}

    (want_loss, want_metrics), want = jax.value_and_grad(
        whole, (0, 1), has_aux=True)(params, x)
    (loss, metrics), got = jax.jit(jax.value_and_grad(
        chunked, (0, 1), has_aux=True))(params, x)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    for name, value in want_metrics.items():
        np.testing.assert_allclose(metrics[name], value, rtol=1e-5)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)
    # The primal: what evaluation runs.
    loss, metrics = chunked(params, x)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(metrics["accuracy"], want_metrics["accuracy"],
                               rtol=1e-5)


def test_the_chunked_loss_keeps_the_dtypes_autodiff_gives():
    """bfloat16 hidden states under float32 parameters, as the cells train:
    d x comes back in x's dtype and d W in the parameter's, each chunk's
    d W rounded to it before it is summed, as the transposed scan did."""
    _, params = _heads()["tied"]
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(2, 16, CFG.hidden_size)), jnp.bfloat16)
    targets = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 16)), jnp.int32)
    cfg = replace(CFG, dtype=jnp.bfloat16)

    def loss(params, x, chunk):
        return lm.next_token_loss(
            lambda h: toy(tied=True).head(params, cfg, h), x, targets, None,
            chunk, 0.0)[0]

    got = jax.grad(loss, (0, 1))(params, x, 8)
    want = jax.grad(loss, (0, 1))(params, x, 0)
    assert got[1].dtype == jnp.bfloat16 and got[0]["wte"].dtype == jnp.float32
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=2e-2, atol=1e-4)


@pytest.mark.parametrize("experts", [True, False])
def test_an_ep_mesh_is_refused_by_name_where_there_are_experts(experts):
    mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1, ep=2),
                      devices=jax.devices()[:2])
    shell = toy(experts=experts)
    params = shell.init(CFG, jax.random.PRNGKey(0))
    set_current_mesh(mesh)
    try:
        if experts:
            with pytest.raises(NotImplementedError,
                               match=r"models/toy\.py does not implement "
                                     "expert parallelism"):
                shell.hidden_states(params, CFG, _tokens(CFG))
        else:
            shell.hidden_states(params, CFG, _tokens(CFG))
    finally:
        set_current_mesh(None)


def test_moe_metrics_count_what_expert_aux_says():
    picked = jnp.zeros((12, 2), jnp.int32)
    whole = lm.expert_aux({"picked": picked,
                           "group_sizes": jnp.array([20, 4])}, (3, 4))
    assert whole["picked"].shape == (3, 4, 2)
    assert int(whole["asked"]) == 24 and int(whole["within_bound"]) == 1
    assert int(whole["rows_summed"]) == 24
    share = lm.expert_aux({"picked": picked, "group_sizes": jnp.array([5, 4]),
                           "asked": jnp.int32(10),
                           "within_bound": jnp.int32(0),
                           "rows_summed": jnp.int32(10)}, (3, 4))
    aux = {name: jnp.stack([whole[name], share[name]]) for name in whole}
    metrics = {k: float(v) for k, v in lm.moe_metrics(aux, 24).items()}
    assert metrics == {
        "moe_assignments": 33.0, "moe_tokens": 34.0, "moe_routed": 48.0,
        "moe_rows_summed": 34.0, "moe_calls": 2.0, "moe_calls_within_bound": 1.0,
        "moe_load_max_over_mean": pytest.approx(20 / 12)}
    assert set(lm.SUMMED_METRICS) < set(metrics) == set(lm.RECORDED_METRICS)
    assert lm.moe_metrics({"floor": jnp.zeros(3)}, 24) == {}


# -- values handed on, a LayerNorm with a bias, constants a layer ------------

def _sharing_block(cfg, kind, h, layer, positions, shared):
    """``counted`` layers hand on their output; ``plain`` layers add the
    last one handed on, times their place in the published model."""
    h, aux = _block(cfg, kind, h, layer, positions)
    if kind == "counted":
        aux = dict(aux, **{lm.HANDED_ON: {"kept": h}})
    elif "kept" in shared:
        h = h + layer["place"] * shared["kept"]
    return h, aux


def _places(cfg, run):
    names = [name for name, _, _ in lm.runs(cfg.layers)]
    depth = lm.runs(cfg.layers)[names.index(run)][2]
    return {"place": jnp.arange(1.0, depth + 1) * (names.index(run) + 1)}


SHARING = ("plain", "counted", "counted", "plain", "plain")


def _sharing_by_hand(params, cfg, tokens):
    """The sharing toy's hidden states, layer by layer, no scan."""
    h = jnp.take(params["wte"], tokens, axis=0)
    kept = None
    for run, kind, depth in lm.runs(cfg.layers):
        for i in range(depth):
            layer = jax.tree.map(lambda a: a[i], params[run])
            h, _ = _block(cfg, kind, h, layer, None)
            if kind == "counted":
                kept = h
            elif kept is not None:
                h = h + _places(cfg, run)["place"][i] * kept
    return lm.rmsnorm(h, params["lnf_scale"], cfg.rms_norm_eps)


@pytest.mark.parametrize("remat", [False, True])
def test_a_run_hands_its_last_layers_values_to_the_runs_behind_it(remat):
    """``shares``: what the last ``counted`` layer hands on reaches both
    ``plain`` layers of the run behind it as one array, scaled by each
    layer's own constant; values and gradients are the layers called one
    by one, and the readers' run carries it as a constant of its scan."""
    cfg = replace(CFG, layers=SHARING, remat=remat)
    shell = lm.Decoder(name="toy", shapes=_shapes, block=_sharing_block,
                       shares=True, constants=_places)
    params = shell.init(cfg, jax.random.PRNGKey(0))
    assert "place" not in params["run00_plain"]
    tokens = _tokens(cfg)
    got, _ = shell.hidden_states(params, cfg, tokens)
    np.testing.assert_allclose(got, _sharing_by_hand(params, cfg, tokens),
                               rtol=1e-5, atol=1e-6)
    weight = jax.random.normal(jax.random.PRNGKey(1), got.shape)
    grads = jax.grad(lambda p: (shell.hidden_states(p, cfg, tokens)[0]
                                * weight).sum())(params)
    want = jax.grad(lambda p: (_sharing_by_hand(p, cfg, tokens) * weight).sum())(
        params)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    jaxpr = jax.make_jaxpr(lambda p: shell.hidden_states(p, cfg, tokens))(
        params)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [1, 2, 2]
    readers = scans[-1]
    consts = [v.aval.shape for v in readers.invars[
        :readers.params["num_consts"]]]
    assert tokens.shape + (cfg.hidden_size,) in consts


def test_a_family_without_shared_values_is_called_as_before():
    """No ``shares``: the block takes five arguments, and an aux under
    ``HANDED_ON``'s name would be a family's own business."""
    cfg = replace(CFG, floors=True)
    got, aux = toy().hidden_states(toy().init(cfg, jax.random.PRNGKey(0)),
                                   cfg, _tokens(cfg))
    assert set(aux) == {"floor", "sizes"} and got.shape[-1] == 8


def test_a_layernorm_family_has_a_final_bias_and_the_same_draws():
    """``final_norm_bias``: one more leaf of zeros, the other leaves drawn
    as without it, and the final norm a LayerNorm with that bias."""
    plain = toy().init(CFG, jax.random.PRNGKey(3))
    shell = toy(final_norm_bias="lnf_bias")
    params = shell.init(CFG, jax.random.PRNGKey(3))
    assert set(params) == set(plain) | {"lnf_bias"}
    np.testing.assert_array_equal(params["lnf_bias"], 0.0)
    for name in plain:
        for a, b in zip(jax.tree.leaves(params[name]),
                        jax.tree.leaves(plain[name])):
            np.testing.assert_array_equal(a, b)
    assert shell.param_specs(CFG, ShardingRules())["lnf_bias"] \
        == shell.param_specs(CFG, ShardingRules())["lnf_scale"]
    params["lnf_bias"] = params["lnf_bias"] + 0.5
    tokens = _tokens(CFG)
    x, _ = shell.hidden_states(params, CFG, tokens)
    np.testing.assert_allclose(x.mean(-1), 0.5, atol=1e-5)
    np.testing.assert_allclose(x.var(-1), 1.0, atol=1e-3)


def test_layernorm_is_torchs():
    torch = pytest.importorskip("torch")
    x = np.random.default_rng(0).normal(size=(3, 5, 16)).astype(np.float32)
    scale, bias = (np.random.default_rng(i).normal(size=16).astype(
        np.float32) for i in (1, 2))
    want = torch.nn.functional.layer_norm(
        torch.from_numpy(x), (16,), torch.from_numpy(scale),
        torch.from_numpy(bias), 1e-5).numpy()
    np.testing.assert_allclose(lm.layernorm(x, scale, bias, 1e-5), want,
                               atol=1e-5)


# -- a second term of the loss, an integer value handed on -------------------

@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("loss_chunk", [0, 4])
def test_a_familys_second_term_is_added_and_differentiated(loss_chunk, masked):
    """``extra_loss``: what ``loss_fn`` returns, and differentiates, is the
    cross-entropy plus the family's term of its blocks' aux; ``loss`` stays
    the cross-entropy (``perplexity`` is of it) and ``total_loss`` is the
    sum. A family without the hook reports no ``total_loss``."""
    cfg = replace(CFG, floors=True, loss_chunk=loss_chunk)
    term = lambda cfg, aux, mask: cfg.multiplier * (aux["floor"] ** 2).sum() \
        * (1.0 if mask is None else mask.sum() / mask.size)
    shell = toy(extra_loss=term)
    params = shell.init(cfg, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: a + 0.1, params)
    tokens, targets = _tokens(cfg), _tokens(cfg)[:, ::-1]
    mask = jnp.ones(tokens.shape).at[0, :2].set(0.0) if masked else None
    plain, plain_metrics = toy().loss_fn(params, cfg, tokens, targets, mask)
    loss, metrics = shell.loss_fn(params, cfg, tokens, targets, mask)
    _, aux = shell.hidden_states(params, cfg, tokens)
    assert "total_loss" not in plain_metrics
    np.testing.assert_allclose(metrics["loss"], plain, rtol=1e-6)
    np.testing.assert_allclose(metrics["perplexity"], jnp.exp(plain),
                               rtol=1e-5)
    np.testing.assert_allclose(loss, plain + term(cfg, aux, mask), rtol=1e-6)
    np.testing.assert_allclose(metrics["total_loss"], loss, rtol=1e-6)
    grads = jax.grad(lambda p: shell.loss_fn(p, cfg, tokens, targets,
                                             mask)[0])(params)
    want = jax.grad(lambda p: toy().loss_fn(p, cfg, tokens, targets, mask)[0]
                    + term(cfg, shell.hidden_states(p, cfg, tokens)[1],
                           mask))(params)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def _masking_block(cfg, kind, h, layer, positions, shared):
    """``counted`` layers hand on which of their outputs are positive, as
    int8; ``plain`` layers behind one keep their own output there alone."""
    out, aux = _block(cfg, kind, h, layer, positions)
    if kind == "counted":
        aux = dict(aux, **{lm.HANDED_ON: {"kept": (out > 0).astype(jnp.int8)}})
    elif "kept" in shared:
        out = h + (out - h) * shared["kept"]
    return out, aux


def _masking_by_hand(params, cfg, tokens):
    h = jnp.take(params["wte"], tokens, axis=0)
    kept = None
    for run, kind, depth in lm.runs(cfg.layers):
        for i in range(depth):
            layer = jax.tree.map(lambda a: a[i], params[run])
            out, _ = _block(cfg, kind, h, layer, None)
            if kind == "counted":
                kept = (out > 0).astype(jnp.int8)
            elif kept is not None:
                out = h + (out - h) * kept
            h = out
    return lm.rmsnorm(h, params["lnf_scale"], cfg.rms_norm_eps)


@pytest.mark.parametrize("remat", [False, True])
def test_an_integer_value_is_handed_on_and_carries_no_cotangent(remat):
    """A handed-on value may be integers (a selection, a mask): it leaves
    its run among the scan's outputs and enters the readers' scan as a
    constant like a float one, the transpose gives it a zero of no dtype,
    and values and gradients are the layers called one by one."""
    cfg = replace(CFG, layers=SHARING, remat=remat)
    shell = lm.Decoder(name="toy", shapes=_shapes, block=_masking_block,
                       shares=True)
    params = shell.init(cfg, jax.random.PRNGKey(0))
    tokens = _tokens(cfg)
    got, _ = shell.hidden_states(params, cfg, tokens)
    np.testing.assert_allclose(got, _masking_by_hand(params, cfg, tokens),
                               rtol=1e-5, atol=1e-6)
    weight = jax.random.normal(jax.random.PRNGKey(1), got.shape)
    grads = jax.jit(jax.grad(lambda p: (shell.hidden_states(
        p, cfg, tokens)[0] * weight).sum()))(params)
    want = jax.grad(lambda p: (_masking_by_hand(p, cfg, tokens)
                               * weight).sum())(params)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    jaxpr = jax.make_jaxpr(lambda p: shell.hidden_states(p, cfg, tokens))(
        params)
    readers = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"][-1]
    consts = [(v.aval.shape, v.aval.dtype) for v in readers.invars[
        :readers.params["num_consts"]]]
    assert (tokens.shape + (cfg.hidden_size,), jnp.int8) in consts


def test_a_kept_selection_is_not_searched_twice():
    """``scan_blocks``' policy keeps what a block names
    ``ops.dsa.SELECTION_NAME`` beside the flash kernel's two: under
    ``full`` the backward pass reads the selection the forward pass made."""
    from jax.ad_checkpoint import checkpoint_name
    from ray_tpu.ops.dsa import SELECTION_NAME
    calls = []

    def block(cfg, kind, h, layer, positions):
        def chosen(x):
            calls.append(1)
            return x > 0
        picked = jax.pure_callback(
            chosen, jax.ShapeDtypeStruct(h.shape, jnp.bool_),
            jax.lax.stop_gradient(h))
        picked = checkpoint_name(picked.astype(jnp.int8), SELECTION_NAME)
        return h + jnp.tanh(h @ layer["w"]) * picked, None

    cfg = replace(CFG, layers=("plain", "plain"), remat=True)
    shell = lm.Decoder(name="toy", shapes=_shapes, block=block)
    params = shell.init(cfg, jax.random.PRNGKey(0))
    jax.block_until_ready(jax.grad(lambda p: shell.hidden_states(
        p, cfg, _tokens(cfg))[0].sum())(params))
    assert len(calls) == 2  # once a layer: the forward pass alone


# -- several prediction heads, a unit offset, float32 logits -----------------

def _heads_by_hand(shell, params, cfg, tokens, targets, mask, heads):
    """The mean, over the heads, of each head's mean cross-entropy over the
    positions whose target lies inside the sequence (and is kept), from
    the whole logits."""
    x, _ = shell.hidden_states(params, cfg, tokens)
    logits = (x @ params["lm_head"]).reshape(*x.shape[:2], heads, -1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    S = targets.shape[1]
    losses = []
    for i in range(heads):
        # Head i at t = 0 .. S - 1 - i against targets t + i.
        nll = -jnp.take_along_axis(logp[:, :S - i, i], targets[:, i:, None],
                                   axis=-1)[..., 0]
        keep = jnp.ones_like(nll) if mask is None else mask[:, i:]
        losses.append((nll * keep).sum() / keep.sum())
    return jnp.stack(losses)


@pytest.mark.parametrize("heads,loss_chunk,masked", [
    (1, 0, False), (3, 0, True), (3, 4, False), (3, 4, True)])
def test_prediction_heads_share_one_hidden_state(heads, loss_chunk, masked):
    """``pred_heads``: ``lm_head`` is [d, heads x vocab], ``head`` returns
    [..., heads, vocab], head i at t is held to token t + 1 + i, the loss is
    the mean of the heads' and its gradient plain autodiff's; ``loss`` and
    ``perplexity`` stay head 0's."""
    cfg = replace(CFG, loss_chunk=loss_chunk, layers=("plain", "counted"))
    shell = toy(pred_heads=lambda cfg: heads)
    params = shell.init(cfg, jax.random.PRNGKey(0))
    assert params["lm_head"].shape == (8, heads * 32)
    tokens, targets = _tokens(cfg), _tokens(cfg)[:, ::-1]
    assert shell.forward(params, cfg, tokens).shape == (2, 6, heads, 32)
    mask = jnp.ones(tokens.shape).at[0, 1:3].set(0.0) if masked else None
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: shell.loss_fn(p, cfg, tokens, targets, mask),
        has_aux=True)(params)
    want, want_grads = jax.value_and_grad(lambda p: _heads_by_hand(
        shell, p, cfg, tokens, targets, mask, heads).mean())(params)
    each = _heads_by_hand(shell, params, cfg, tokens, targets, mask, heads)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(metrics["total_loss"], want, rtol=1e-6)
    np.testing.assert_allclose(metrics["loss"], each[0], rtol=1e-6)
    np.testing.assert_allclose(metrics["perplexity"], jnp.exp(each[0]),
                               rtol=1e-5)
    for i in range(heads):
        np.testing.assert_allclose(metrics[f"mbp_loss_{i}"], each[i],
                                   rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_a_unit_offset_norm_draws_zeros_and_scales_by_one_plus_them():
    """``unit_offset``: the final norm's leaf is drawn zero, the other
    leaves as without it, and the scale is 1 + the leaf."""
    plain, shell = toy(), toy(unit_offset=True)
    params = shell.init(CFG, jax.random.PRNGKey(3))
    ones = plain.init(CFG, jax.random.PRNGKey(3))
    np.testing.assert_array_equal(params["lnf_scale"], 0.0)
    tokens = _tokens(CFG)
    np.testing.assert_array_equal(
        shell.hidden_states(params, CFG, tokens)[0],
        plain.hidden_states(ones, CFG, tokens)[0])
    params["lnf_scale"] = params["lnf_scale"] + 0.5
    np.testing.assert_allclose(
        shell.hidden_states(params, CFG, tokens)[0],
        1.5 * plain.hidden_states(ones, CFG, tokens)[0], rtol=1e-6)


def test_float32_logits_and_the_tops_own_std():
    """``fp32_logits``: the head's product leaves in float32 whatever
    ``cfg.dtype``; ``top_std``: what ``wte`` and ``lm_head`` are drawn
    at."""
    cfg = replace(CFG, hidden_size=256, vocab_size=512)
    x = jnp.ones((2, 3, 256), jnp.bfloat16)
    for fp32, dtype in ((False, jnp.bfloat16), (True, jnp.float32)):
        shell = toy(fp32_logits=fp32)
        params = shell.init(cfg, jax.random.PRNGKey(0))
        assert shell.head(params, replace(cfg, dtype=jnp.bfloat16),
                          x).dtype == dtype
    narrow = toy(top_std=lambda cfg: 0.002).init(cfg, jax.random.PRNGKey(0))
    for name in ("wte", "lm_head"):
        assert abs(float(narrow[name].std()) - 0.002) < 2e-4
        assert abs(float(params[name].std()) - 0.02) < 2e-3
