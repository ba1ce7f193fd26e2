"""Pipeline parallelism, Ulysses attention.

All run on the 8-device virtual CPU mesh (conftest.py). These cover the
parallelism strategies the reference lacks entirely (SURVEY.md §2.5:
TP/PP/SP/EP rows marked 'no').
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel import (MeshConfig, build_mesh, make_pipeline_fn,
                              sequential_apply, stage_param_specs)


def test_pipeline_matches_sequential():
    n_stages, n_micro, mb, dim = 4, 8, 2, 16
    mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=1, sp=1, ep=1, pp=4))

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    key = jax.random.PRNGKey(0)
    kw, kb, kx = jax.random.split(key, 3)
    stage_params = {
        "w": jax.random.normal(kw, (n_stages, dim, dim)) * 0.3,
        "b": jax.random.normal(kb, (n_stages, dim)) * 0.1,
    }
    xs = jax.random.normal(kx, (n_micro, mb, dim))

    from ray_tpu.parallel.sharding import tree_shardings
    sharded_params = jax.device_put(
        stage_params, tree_shardings(mesh, stage_param_specs(stage_params)))

    pipelined = make_pipeline_fn(stage_fn, n_stages, mesh)
    out_pipe = jax.jit(pipelined)(sharded_params, xs)
    out_seq = sequential_apply(stage_fn, stage_params, xs)
    np.testing.assert_allclose(np.asarray(out_pipe), np.asarray(out_seq),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_gradients_flow():
    n_stages, n_micro, mb, dim = 2, 4, 2, 8
    mesh = build_mesh(MeshConfig(dp=4, fsdp=1, tp=1, sp=1, ep=1, pp=2))

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    stage_params = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (n_stages, dim, dim))
        * 0.3}
    xs = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, dim))
    pipelined = make_pipeline_fn(stage_fn, n_stages, mesh)

    def loss_pipe(p):
        return (pipelined(p, xs) ** 2).sum()

    def loss_seq(p):
        return (sequential_apply(stage_fn, p, xs) ** 2).sum()

    g_pipe = jax.jit(jax.grad(loss_pipe))(stage_params)
    g_seq = jax.grad(loss_seq)(stage_params)
    np.testing.assert_allclose(np.asarray(g_pipe["w"]),
                               np.asarray(g_seq["w"]), rtol=1e-4, atol=1e-5)


def test_ulysses_matches_exact_attention():
    from ray_tpu.ops.ulysses import (_full_causal_attention,
                                     make_ulysses_attention)
    mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=1, sp=4, ep=1))
    B, S, H, D = 2, 32, 4, 8
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D))
    k = jax.random.normal(kk, (B, S, H, D))
    v = jax.random.normal(kv, (B, S, H, D))
    attn = make_ulysses_attention(mesh)
    out = jax.jit(attn)(q, k, v)
    ref = _full_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    from ray_tpu.ops.ulysses import make_ulysses_attention
    mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=1, sp=4, ep=1))
    attn = make_ulysses_attention(mesh)
    q = jnp.zeros((1, 16, 3, 8))  # 3 heads not divisible by sp=4
    with pytest.raises(ValueError):
        attn(q, q, q)
