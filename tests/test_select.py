"""ops/select.py: the device's exact top-k by magnitude without a sort
selects the set ``lax.top_k(abs(x), k)`` selects, ties at the k-th value
cut at the lowest index, and keys of one size share one traced body."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.ops import select

SIZES = [1, 5, 127, 128, 129, 768, 10_007, 300_000]


def _values(kind: str, n: int) -> np.ndarray:
    rng = np.random.default_rng(n)
    x = rng.standard_normal(n).astype(np.float32)
    if kind == "normal":
        return x
    if kind == "ties":
        # half-integers: many equals at the k-th magnitude, of both signs
        return (np.round(x * 2) / 2).astype(np.float32)
    if kind == "zeros":
        live = rng.random(n) < 0.005
        return np.where(live, x, np.where(rng.random(n) < 0.3, -0.0, 0.0)
                        ).astype(np.float32)
    if kind == "equal":
        return np.full(n, -1.5, np.float32)
    if kind == "infinity":
        x[rng.integers(n)] = -np.inf
        return x
    raise ValueError(kind)


@pytest.mark.parametrize("threshold", [0.01, 0.3])
@pytest.mark.parametrize("kind", ["normal", "ties", "zeros", "equal",
                                  "infinity"])
@pytest.mark.parametrize("n", SIZES)
def test_selects_what_top_k_selects(n, kind, threshold):
    k = max(int(n * threshold), 1)
    x = _values(kind, n)
    pos, val = map(np.asarray, jax.jit(
        select.topk_by_magnitude, static_argnums=1)(jnp.asarray(x), k))
    assert pos.shape == (k,) and pos.dtype == np.int32
    assert pos.min() >= 0 and pos.max() < n
    assert np.all(np.diff(pos) > 0), "ascending, so distinct"
    # lax.top_k breaks ties by the lowest index, and so does a stable
    # argsort of the negated magnitudes
    _mags, ref = jax.lax.top_k(jnp.abs(jnp.asarray(x)), k)
    assert set(pos.tolist()) == set(np.asarray(ref).tolist())
    stable = np.argsort(-np.abs(x), kind="stable")[:k]
    np.testing.assert_array_equal(pos, np.sort(stable))
    # the values are x there, to the sign of a zero
    np.testing.assert_array_equal(val.view(np.int32), x[pos].view(np.int32))


def test_refuses_k_outside_the_key():
    with pytest.raises(ValueError):
        select.topk_by_magnitude(jnp.zeros(4), 5)
    with pytest.raises(ValueError):
        select.topk_by_magnitude(jnp.zeros(4), 0)


@pytest.mark.parametrize("side_by_side", [True, False])
def test_keys_of_one_size_share_one_counting_loop(side_by_side,
                                                  monkeypatch):
    """24 equal-sized keys lower to ONE selection body, not 24: side by
    side the only loop is the counting loop; one after the other there
    is the loop over keys and the counting loop inside it."""
    if not side_by_side:
        monkeypatch.setattr(select, "_SIDE_BY_SIDE_ELEMS", 0)
    size, k, keys = 1000, 10, 24
    offsets = [13 + i * size for i in range(keys)]
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.standard_normal(13 + keys * size + 5)
                    .astype(np.float32))
    fn = jax.jit(lambda v: select.topk_flat(
        v, offsets, [size] * keys, [k] * keys))
    text = fn.lower(v).as_text()
    assert text.count("stablehlo.while") == (1 if side_by_side else 2)
    got, vals = map(np.asarray, fn(v))
    for g, off in enumerate(offsets):
        _m, ref = jax.lax.top_k(jnp.abs(v[off:off + size]), k)
        np.testing.assert_array_equal(
            got[g * k:(g + 1) * k], np.sort(np.asarray(ref)) + off)
    np.testing.assert_array_equal(vals, np.asarray(v)[got])


def test_flat_selection_keeps_key_order_across_sizes():
    """Keys of three sizes interleaved: the flat positions come back in
    key order whatever the grouping by size."""
    sizes = [300, 7, 300, 128, 7, 300]
    ks = [max(int(s * 0.1), 1) for s in sizes]
    offsets = np.concatenate([[0], np.cumsum(sizes)])[:-1].tolist()
    rng = np.random.default_rng(1)
    v = jnp.asarray(rng.standard_normal(sum(sizes)).astype(np.float32))
    got, vals = map(np.asarray, jax.jit(lambda v: select.topk_flat(
        v, offsets, sizes, ks))(v))
    want = np.concatenate([
        np.sort(np.asarray(jax.lax.top_k(jnp.abs(v[o:o + s]), k)[1])) + o
        for o, s, k in zip(offsets, sizes, ks)])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(vals, np.asarray(v)[want])
    assert np.all(np.diff(got) > 0), "keys in flat order: all ascending"
