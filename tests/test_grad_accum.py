"""Gradient accumulation + transformer remat."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.parallel.grad_accum import accumulate_gradients


def test_accum_matches_full_batch():
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    X = jnp.asarray(rng.normal(size=(12, 6)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(12,)), jnp.float32)

    def grad_fn(params, X, y):
        def loss_fn(p):
            return jnp.mean((X @ p - y) ** 2)
        return jax.value_and_grad(loss_fn)(params)

    full_loss, full_grad = grad_fn(w, X, y)
    for m in (1, 2, 3, 4, 6):
        acc = jax.jit(accumulate_gradients(grad_fn, m))
        loss, grad = acc(w, X, y)
        np.testing.assert_allclose(float(loss), float(full_loss),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(grad), np.asarray(full_grad),
                                   rtol=1e-4, atol=1e-6)


def test_accum_validation():
    def grad_fn(p, X, y):
        return jnp.float32(0), p

    with pytest.raises(ValueError, match=">= 1"):
        accumulate_gradients(grad_fn, 0)
    fn = accumulate_gradients(grad_fn, 5)
    with pytest.raises(ValueError, match="divisible"):
        fn(jnp.zeros(3), jnp.zeros((12, 2)), jnp.zeros(12))


def test_accum_with_mesh_pmean():
    from geomx_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices())  # dp=8
    rng = np.random.RandomState(1)
    w = jnp.asarray(rng.normal(size=(4,)), jnp.float32)
    X = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(16,)), jnp.float32)

    def grad_fn(params, X, y):
        def loss_fn(p):
            return jnp.mean((X @ p - y) ** 2)
        return jax.value_and_grad(loss_fn)(params)

    from jax.sharding import PartitionSpec as P

    inner = accumulate_gradients(grad_fn, 2, axis_name="dp")
    fn = jax.jit(jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P("dp"), P("dp")), out_specs=(P(), P()),
        check_vma=False))
    loss, grad = fn(w, X, y)
    full_loss, full_grad = grad_fn(w, X, y)
    np.testing.assert_allclose(float(loss), float(full_loss), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(full_grad),
                               rtol=1e-4, atol=1e-6)


def test_transformer_remat_same_values():
    from geomx_tpu.models.transformer import Transformer

    tok = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
    plain = Transformer(vocab=64, dim=32, depth=2, heads=2, max_len=32)
    remat = Transformer(vocab=64, dim=32, depth=2, heads=2, max_len=32,
                        remat=True)
    p = plain.init(jax.random.PRNGKey(1), tok)
    np.testing.assert_allclose(np.asarray(plain.apply(p, tok)),
                               np.asarray(remat.apply(p, tok)),
                               rtol=1e-6, atol=1e-6)

    def loss(model, p):
        return jnp.mean(model.apply(p, tok) ** 2)

    gp = jax.grad(lambda p: loss(plain, p))(p)
    gr = jax.grad(lambda p: loss(remat, p))(p)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_accum_preserves_param_dtype_and_single_array_batch():
    w = jnp.ones((4,), jnp.bfloat16)
    X = jnp.ones((8, 4), jnp.float32)

    def grad_fn(p, X):  # X-only loss: no labels needed
        def loss_fn(p):
            return jnp.mean((X @ p.astype(jnp.float32)) ** 2)
        return jax.value_and_grad(loss_fn)(p)

    loss, grad = accumulate_gradients(grad_fn, 4)(w, X)
    assert grad.dtype == jnp.bfloat16
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("m", [1, 2, 4])
def test_accum_sums_the_counts_of_a_grad_fn_with_aux(m):
    rng = np.random.RandomState(2)
    w = jnp.asarray(rng.normal(size=(5,)), jnp.float32)
    X = jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)

    def loss_fn(p, X):
        out = X @ p
        return jnp.mean(out ** 2), {"positive": jnp.sum(out > 0),
                                    "rows": jnp.float32(X.shape[0])}

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    (full_loss, full_aux), full_grad = grad_fn(w, X)
    (loss, aux), grad = jax.jit(
        accumulate_gradients(grad_fn, m, has_aux=True))(w, X)
    np.testing.assert_allclose(float(loss), float(full_loss), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(full_grad),
                               rtol=1e-4, atol=1e-6)
    assert int(aux["positive"]) == int(full_aux["positive"])
    assert float(aux["rows"]) == 8.0
