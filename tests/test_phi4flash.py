"""models/phi4flash.py (Mamba-1 layers, differential attention over a window,
causal and onto another layer's K/V, gated memory units, one memory and one
K/V made mid-stack and read by every layer behind) against the installed
``transformers``' ``MambaMixer.slow_forward`` and ``DiffLlamaAttention`` for
the two mixers, and against a copy of the benchmark's plain reference for the
whole model, through ``family_cases.py``, every rung of the benchmark's cut
included; what the middle pair hands on: its gradient is the sum over its
readers, and a step holds one copy of it.
"""

from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_cases
import reference_phi4flash as reference
from family_cases import Patches, batch, drawn, forward_alone
from ray_tpu.models import lm, phi4flash
from ray_tpu.ops import selective_scan as scan_op
from ray_tpu.parallel import MeshConfig, build_mesh

CFG = phi4flash.config("phi4flash-tiny")
SEQ = 64
# The kernels (interpreted: the flash three with and without a window, the
# scan's pair and the convolution's, at a length that tiles), remat and the
# chunked loss.
FLASH = replace(CFG, attn_impl="flash", attn_blk_q=128, attn_blk_k=128,
                remat=True, loss_chunk=128)
FLASH_SEQ = scan_op.CHUNK
# The benchmark's rungs (self, middle, cross pairs) of the published 32.
RUNGS = {"4-1-3": (4, 3), "3-1-3": (3, 3), "2-1-2": (2, 2), "1-1-1": (1, 1),
         "whole": (8, 7)}


def published(cfg):
    return {"num_hidden_layers": len(cfg.layers),
            "layers_run": list(cfg.layers),
            "reduced": {"num_hidden_layers": {
                "published": cfg.num_hidden_layers}},
            "hidden_size": cfg.hidden_size,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "sliding_window": cfg.sliding_window,
            "layer_norm_eps": cfg.layer_norm_eps,
            "assumed": {"mamba_sizes": {
                "mamba_d_state": cfg.mamba_d_state,
                "mamba_dt_rank": cfg.dt_rank}}}


def moved(name, leaf, key):
    """Every vector off its one or zero, as the benchmark's
    ``draw_vectors`` does, and Wq, Wk times 1.5."""
    if name.endswith(("wq']", "wk']")):
        return 1.5 * leaf
    if leaf.ndim == (2 if "run" in name else 1) or name.endswith(
            ("A_log']", "bq']", "bk']", "bv']")):
        return leaf + 0.1 * jax.random.normal(key, leaf.shape)
    return leaf


PHI = family_cases.Family(
    module=phi4flash, reference=reference, cfg=CFG, seq=SEQ, flash=FLASH,
    flash_seq=FLASH_SEQ, published=published, moved=moved,
    reference_forward_traces=False, logits_tol=1e-4, rms_floor=0.05,
    accum_steps=(1,), bfloat16=replace(FLASH, dtype=jnp.bfloat16),
    flash_kernels=("selective_scan_fwd", "selective_scan_bwd",
                   "conv_silu_fwd", "conv_silu_bwd", "flash_fwd_win",
                   "flash_bwd_win", "flash_fwd", "flash_bwd/"),
    wrong=(dict(layers_run=(0, 1, 2)), dict(layers_run=(1, 2)),
           dict(layers_run=(2, 3, 0, 1)), dict(layers_run=(0, 1, 6, 7)),
           dict(layers_run=(0, 1, 8, 9)), dict(num_key_value_heads=4),
           dict(num_attention_heads=6, num_key_value_heads=3),
           dict(mb_per_layer=1), dict(tie_word_embeddings=False)),
    wrong_ids=lambda wrong: "-".join(f"{k}={v}" for k, v in wrong.items()),
    refuses=(ValueError, NotImplementedError))
globals().update(family_cases.cases(PHI))


def test_the_tiny_stack_has_all_three_kinds_of_pair():
    assert phi4flash._runs(CFG) == (
        ("run00_self", "self", 2), ("run01_middle", "middle", 1),
        ("run02_cross", "cross", 1))
    assert phi4flash._runs(phi4flash.config("phi-4-mini-flash-reasoning")) \
        == (("run00_self", "self", 8), ("run01_middle", "middle", 1),
            ("run02_cross", "cross", 7))


# -- against transformers' two mixers --------------------------------------

def test_the_mamba_mixer_is_transformers_slow_forward():
    """``MambaMixer.slow_forward`` is the Mamba layer term for term: the
    split of the projection, the taps' order and the bias, ``x_proj``'s
    split, ``dt_proj`` with its bias under the softplus, ``-exp(A_log)``,
    the recurrence, ``D``, the gate and the projection back."""
    torch = pytest.importorskip("torch")
    try:
        from transformers.models.mamba.configuration_mamba import MambaConfig
        from transformers.models.mamba.modeling_mamba import MambaMixer
    except ImportError as exc:
        pytest.skip(f"no mamba in transformers: {exc}")
    cfg = CFG
    theirs = MambaMixer(MambaConfig(
        hidden_size=cfg.hidden_size, state_size=cfg.mamba_d_state,
        conv_kernel=cfg.mamba_d_conv, expand=cfg.mamba_expand,
        time_step_rank=cfg.dt_rank, use_bias=False, use_conv_bias=True,
        hidden_act="silu"), layer_idx=0).float().eval()
    w = {name[2:]: leaf[0] for name, leaf in drawn(PHI, cfg)["run00_self"].items()
         if name.startswith("a_")}
    as_torch = lambda a: torch.from_numpy(np.array(a, np.float32))  # noqa: E731
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, cfg.hidden_size))
    with torch.no_grad():
        theirs.in_proj.weight.copy_(as_torch(w["w_in"].T))
        theirs.conv1d.weight.copy_(as_torch(w["conv_w"].T[:, None, :]))
        theirs.conv1d.bias.copy_(as_torch(w["conv_b"]))
        theirs.x_proj.weight.copy_(as_torch(w["w_x"].T))
        theirs.dt_proj.weight.copy_(as_torch(w["w_dt"].T))
        theirs.dt_proj.bias.copy_(as_torch(w["b_dt"]))
        theirs.A_log.copy_(as_torch(w["A_log"]))
        theirs.D.copy_(as_torch(w["D"]))
        theirs.out_proj.weight.copy_(as_torch(w["w_out"].T))
        want = theirs.slow_forward(as_torch(x)).numpy()
    with jax.default_matmul_precision("highest"):
        got, _, _ = jax.jit(partial(phi4flash._mamba, cfg))(x, w)
    rms = float(np.sqrt((want ** 2).mean()))
    assert rms > 1e-3
    np.testing.assert_allclose(got, want, atol=1e-4 * rms)


@pytest.mark.parametrize("layer_idx", [1, 17])
def test_differential_attention_is_diffllamas_under_the_head_permutation(
        layer_idx):
    """``DiffLlamaAttention`` pairs head j with j + heads / 2 (and their k
    heads j // 2 with j // 2 + kv heads / 2) where this model pairs 2j with
    2j + 1 (k heads 2g with 2g + 1): with the columns of Wq, Wk, Wv
    permuted so, rope the identity and its norm without scale, it is this
    attention: the two maps, lambda with ``l0`` of the layer, ``V_g``, the
    norm over a differential head, ``1 - l0``."""
    torch = pytest.importorskip("torch")
    try:
        from transformers.models.diffllama.configuration_diffllama import \
            DiffLlamaConfig
        from transformers.models.diffllama.modeling_diffllama import \
            DiffLlamaAttention
    except ImportError as exc:
        pytest.skip(f"no diffllama in transformers: {exc}")
    cfg = replace(CFG, num_attention_heads=8, num_key_value_heads=4)
    d, h, kv, hd = cfg.hidden_size, 8, 4, cfg.head_dim
    theirs = DiffLlamaAttention(DiffLlamaConfig(
        hidden_size=d, num_attention_heads=h, num_key_value_heads=kv,
        attention_bias=True, rms_norm_eps=1e-5, attention_dropout=0.0),
        layer_idx=layer_idx).float().eval()
    w = {name[2:]: leaf[0]
         for name, leaf in drawn(PHI, cfg)["run01_middle"].items()
         if name.startswith("b_")}
    w["subln_scale"] = jnp.ones_like(w["subln_scale"])
    # Their head j + p * heads / 2 is this model's 2j + p.
    q_of = np.array([2 * (t % (h // 2)) + t // (h // 2) for t in range(h)])
    k_of = np.array([2 * (t % (kv // 2)) + t // (kv // 2)
                     for t in range(kv)])
    as_torch = lambda a: torch.from_numpy(np.array(a, np.float32))  # noqa: E731
    x = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, d))
    mask = torch.full((SEQ, SEQ), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        theirs.q_proj.weight.copy_(as_torch(
            w["wq"][:, q_of].reshape(d, -1).T))
        theirs.q_proj.bias.copy_(as_torch(w["bq"][q_of].reshape(-1)))
        theirs.k_proj.weight.copy_(as_torch(
            w["wk"][:, k_of].reshape(d, -1).T))
        theirs.k_proj.bias.copy_(as_torch(w["bk"][k_of].reshape(-1)))
        theirs.v_proj.weight.copy_(as_torch(
            w["wv"][:, k_of].reshape(d, -1).T))
        theirs.v_proj.bias.copy_(as_torch(w["bv"][k_of].reshape(-1)))
        theirs.o_proj.weight.copy_(as_torch(w["wo"].reshape(-1, d).T))
        theirs.o_proj.bias.copy_(as_torch(w["bo"]))
        for name in ("q1", "k1", "q2", "k2"):
            getattr(theirs, "lambda_" + name).copy_(
                as_torch(w["lambda_" + name]))
        ones, zeros = torch.ones(2, SEQ, hd), torch.zeros(2, SEQ, hd)
        want = theirs(as_torch(x), (ones, zeros), attention_mask=mask
                      )[0].numpy()
    with jax.default_matmul_precision("highest"):
        got, _, _ = jax.jit(partial(phi4flash._differential, cfg))(
            x, w, jnp.float32(phi4flash.lambda_init(layer_idx)))
    rms = float(np.sqrt((want ** 2).mean()))
    assert rms > 1e-2
    np.testing.assert_allclose(got, want, atol=1e-4 * rms)


# -- against the reference ------------------------------------------------

@pytest.mark.parametrize("which", ["both", "both_flash"])
def test_the_loss_matches_to_five_places(which, request):
    got, want = request.getfixturevalue(which)["loss"]
    assert float(got) == pytest.approx(float(want), abs=1e-5)


@pytest.mark.parametrize("leaf", family_cases.leaves(PHI))
@pytest.mark.parametrize("which", ["both", "both_flash"])
def test_gradients_match_the_reference(which, leaf, request):
    got, want = (family_cases.by_name(tree) for tree in
                 request.getfixturevalue(which)["grads"])
    norm = float(jnp.linalg.norm(want[leaf]))
    if leaf.endswith("b_bk']"):
        # A constant added to every key moves no softmax: zero but for
        # rounding, on both sides.
        scale = float(jnp.linalg.norm(want[leaf.replace("b_bk", "b_bq")]))
        assert norm < 1e-4 * scale and \
            float(jnp.linalg.norm(got[leaf])) < 1e-4 * scale
        return
    assert norm > 0.0
    # A lambda's gradient is a sum over every output of terms that nearly
    # cancel (1e-4 of a bias's): ten times the room.
    room = 1e-3 if "lambda" in leaf else 1e-4
    assert float(jnp.linalg.norm(got[leaf] - want[leaf])) < room * norm


@pytest.mark.parametrize("rung", RUNGS)
def test_a_rung_of_the_cut_is_those_layers_of_the_whole_model(rung):
    """Every rung of the benchmark's cut, and all 32 published indices:
    the program on ``layers_run`` is the reference walking those layers of
    the published 32 under their own ``l0(i)``, on the whole model's
    stacks cut to them."""
    n_self, n_cross = RUNGS[rung]
    wide = replace(CFG, num_hidden_layers=32, layers_run=None)
    params = drawn(PHI, wide)
    layers = tuple(range(2 * n_self)) + (16, 17) \
        + tuple(range(18, 18 + 2 * n_cross))
    cfg = replace(wide, layers_run=None if rung == "whole" else layers)
    assert cfg.layers == layers
    cut = dict(params,
               run00_self=jax.tree.map(lambda a: a[:n_self],
                                       params["run00_self"]),
               run02_cross=jax.tree.map(lambda a: a[:n_cross],
                                        params["run02_cross"]))
    tokens, targets = batch(cfg, SEQ, rows=1)
    where = jnp.broadcast_to(jnp.arange(SEQ, dtype=jnp.int32), tokens.shape)
    want, _, rms = reference.forward(cut, tokens, targets, where,
                                     **reference.arguments(published(cfg)))
    got = forward_alone(PHI, cut, cfg, tokens)
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(rms)
    # The layers' own l0: by the place among those that run it is another
    # function wherever the cut skips a pair.
    if rung not in ("whole", "4-1-3"):
        assert [phi4flash.lambda_init(i) for i in layers] != \
            [phi4flash.lambda_init(i) for i in range(len(layers))]


def _zeroed(params, leaf, change=jnp.zeros_like):
    return {name: dict(stack, **{leaf: change(stack[leaf])})
            if isinstance(stack, dict) and leaf in stack else stack
            for name, stack in params.items()}


#: A cut of a published depth of 12, so that a layer's place and its index
#: differ.
DROP_CFG = replace(CFG, num_hidden_layers=12, sliding_window=16,
                   layers_run=(0, 1, 2, 3, 6, 7, 10, 11))


@pytest.fixture(scope="module")
def cut_logits():
    tokens, _ = batch(DROP_CFG, SEQ)
    return forward_alone(PHI, drawn(PHI, DROP_CFG), DROP_CFG, tokens)


@pytest.mark.parametrize("dropped", [
    "p2_not_subtracted", "subln", "one_minus_l0", "l0_of_the_cut",
    "k_pairing", "window", "memory_after_gate", "gmu_gate", "skip_d", "b_dt",
    "a_tap", "layernorm_bias", "eight_bit_residual"])
def test_a_dropped_term_shows(cut_logits, dropped, monkeypatch):
    """Each term of ISSUE 42's list taken out of the program, on
    ``DROP_CFG``: the logits move by far more than float32 does (a cross
    layer reading k, v of its own input is the benchmark's own test)."""
    cfg = DROP_CFG
    params, run = drawn(PHI, cfg), cfg
    patches = Patches(monkeypatch)
    patch = partial(patches.setattr, phi4flash)
    if dropped == "p2_not_subtracted":
        patch("_lambda", lambda layer, l0: jnp.float32(0.0))
    elif dropped == "subln":
        patch("_subln", lambda o, scale, l0: o * (1.0 - l0))
    elif dropped == "one_minus_l0":
        patch("_subln", lambda o, scale, l0: lm.rmsnorm(o, scale, 1e-5))
    elif dropped == "l0_of_the_cut":
        plain = phi4flash.lambda_init
        patch("lambda_init", lambda i: plain(cfg.layers.index(i)))
    elif dropped == "k_pairing":
        plain_heads = phi4flash._to_query_heads
        patch("_to_query_heads", lambda c, k, v: plain_heads(
            c, k.reshape(k.shape[:2] + (-1, 2, k.shape[3]))[:, :, :, ::-1]
            .reshape(k.shape), v))
    elif dropped == "window":
        run = replace(cfg, sliding_window=1 << 30)
    elif dropped == "memory_after_gate":
        plain_mamba = phi4flash._mamba

        def gated(c, x, layer):
            out, y, floor = plain_mamba(c, x, layer)
            return out, y * jax.nn.silu(
                (x @ layer["w_in"])[..., c.d_inner:]), floor
        patch("_mamba", gated)
    elif dropped == "gmu_gate":
        patch("_gmu", lambda c, x, layer, m: m @ layer["w_o"])
    elif dropped == "eight_bit_residual":
        plain_block = phi4flash._block
        patch("_block", lambda c, kind, h, *rest, **kw: plain_block(
            c, kind, h.astype(jnp.float8_e4m3fn).astype(h.dtype), *rest,
            **kw))
    else:
        leaf, change = {
            "skip_d": ("a_D", jnp.zeros_like),
            "b_dt": ("a_b_dt", jnp.zeros_like),
            "a_tap": ("a_conv_w", lambda w: w.at[:, 0].set(0.0)),
            "layernorm_bias": ("a_ln1_bias", jnp.zeros_like)}[dropped]
        params = _zeroed(params, leaf, change)
    tokens, _ = batch(cfg, SEQ)
    got = forward_alone(PHI, params, run, tokens, patched=patches.made)
    want = cut_logits
    rms = float(jnp.sqrt((want ** 2).mean()))
    assert float(jnp.sqrt(((got - want) ** 2).mean())) > 2e-5 * rms
    assert float(jnp.abs(got - want).max()) > 1e-4 * rms


# -- what the middle pair hands on -------------------------------------------

def test_the_shared_values_gradient_is_the_sum_over_their_readers():
    """The cotangent the middle pair receives for m, k and v is the sum of
    what each cross pair, reading them alone, sends back: two cross pairs
    through the shell's one scan against each pair called by hand."""
    cfg = replace(CFG, num_hidden_layers=12,
                  layers_run=(0, 1, 6, 7, 8, 9, 10, 11))
    params = drawn(PHI, cfg)
    tokens, _ = batch(cfg, SEQ, rows=1)
    positions = lm.positions_of(tokens)
    h = jax.random.normal(jax.random.PRNGKey(11),
                          tokens.shape + (cfg.hidden_size,))
    shared = {"m": jax.random.normal(jax.random.PRNGKey(12),
                                     tokens.shape + (cfg.d_inner,)),
              **{n: jax.random.normal(
                  jax.random.PRNGKey(13 + i), tokens.shape + (
                      cfg.num_key_value_heads, cfg.head_dim))
                 for i, n in enumerate("kv")}}
    stack = dict(params["run02_cross"],
                 **phi4flash._constants(cfg, "run02_cross"))
    assert jax.tree.leaves(stack)[0].shape[0] == 2
    weight = jax.random.normal(jax.random.PRNGKey(20), h.shape)

    def through_the_scan(shared):
        out, _ = lm.scan_blocks(
            cfg, partial(phi4flash._block, cfg, "cross", shared=shared), h,
            stack, positions)
        return (out * weight).sum()

    def one_reader(shared, index, h_in, cotangent):
        layer = jax.tree.map(lambda a: a[index], stack)
        out, vjp = jax.vjp(lambda s, h_: phi4flash._block(
            cfg, "cross", h_, layer, positions, s)[0], shared, h_in)
        return out, vjp(cotangent)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(through_the_scan)(shared)
        # By hand: the second pair's cotangent first, then the first's.
        h1, _ = one_reader(shared, 0, h, jnp.zeros_like(h))
        _, (from_second, into_first) = one_reader(shared, 1, h1, weight)
        _, (from_first, _) = one_reader(shared, 0, h, into_first)
    for name in shared:
        want = from_first[name] + from_second[name]
        assert float(jnp.abs(from_second[name]).max()) > 0.0
        np.testing.assert_allclose(
            got[name], want, rtol=0,
            atol=1e-5 * float(jnp.abs(want).max()))


def test_a_step_holds_one_copy_of_what_is_handed_on():
    """In the differentiated, rematerialised loss the memory [B, S,
    d_inner] is an output of the middle run's scan and a constant of the
    cross run's: no array carries it (or k, v) once a cross layer, neither
    the scans' stacked outputs nor their carries."""
    cfg = replace(FLASH, attn_impl="dot", num_hidden_layers=12,
                  layers_run=(0, 1, 6, 7, 8, 9, 10, 11))
    params = drawn(PHI, cfg)
    tokens, targets = batch(cfg, SEQ, rows=1)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: phi4flash.loss_fn(
        p, cfg, tokens, targets)[0]))(params)
    m_shape = tokens.shape + (cfg.d_inner,)
    kv_shape = tokens.shape + (cfg.num_key_value_heads, cfg.head_dim)
    scans = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "scan"]
    assert len(scans) >= 6  # three runs forward, three backward, the loss
    as_constants = 0
    for eqn in scans:
        n_consts = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        consts = [v.aval.shape for v in eqn.invars[:n_consts]]
        as_constants += m_shape in consts
        # Stacked over a run of two cross pairs it would be [2, B, S, ...].
        for var in list(eqn.invars[n_consts:]) + list(eqn.outvars[n_carry:]):
            assert var.aval.shape[1:] not in (m_shape, kv_shape) \
                or var.aval.shape[0] == 1, (eqn.params["length"],
                                            var.aval.shape)
    assert as_constants >= 2  # the cross run's forward and its backward


# -- training, the mesh ---------------------------------------------------------

def test_trains_and_feeds_the_two_gauges():
    """The two gauges say what the step saw: steps of 0.001-0.1 (and what
    the projection adds) times rates of 1-16; l0 of the layers that run,
    0.36-0.73, and a little."""
    found = family_cases.trained(PHI, 1)
    for metrics in found["metrics"]:
        assert -8.0 < metrics["selective_scan_decay_floor"] < -0.5
        assert 0.5 < metrics["diff_attention_lambda_max"] < 1.0
    gauges = found["gauges"]
    assert -8.0 < gauges["ray_tpu_train_selective_scan_decay_floor"] < -0.5
    assert 0.5 < gauges["ray_tpu_train_diff_attention_lambda_max"] < 1.0


def test_a_data_parallel_mesh_runs_the_kernels_per_shard(both_flash):
    """Under dp = 2 the scan's, the convolution's and the flash kernels run
    per shard of the batch, and the loss is the one-device loss."""
    from ray_tpu.parallel import mesh as mesh_mod
    tokens, targets = batch(FLASH, FLASH_SEQ)
    mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=1),
                      devices=jax.devices()[:2])
    previous = mesh_mod.current_mesh()
    mesh_mod.set_current_mesh(mesh)
    try:
        with mesh:
            got = float(jax.jit(lambda p: phi4flash.loss_fn(
                p, FLASH, tokens, targets)[0])(drawn(PHI, FLASH)))
    finally:
        mesh_mod.set_current_mesh(previous)
    assert got == pytest.approx(float(both_flash["loss"][0]), abs=1e-5)
