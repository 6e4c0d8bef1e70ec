"""ops/select.py: the device's exact top-k by magnitude without a sort
selects the set ``lax.top_k(abs(x), k)`` selects, ties at the k-th value
cut at the lowest index, keys of one size share one traced body, and
the rule it hands out beside the positions (a threshold and a cut) is
the mask of exactly those positions."""

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
    pos, val, t, cut = map(np.asarray, jax.jit(
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
    # and the rule is the mask of these positions, no more and no fewer
    np.testing.assert_array_equal(
        np.flatnonzero(select.leaving(jnp.asarray(x), t, cut)), pos)


def _rule_case(case: str):
    """(x, k) of one equal-sized key: what the rule has to get right."""
    rng = np.random.default_rng(5)
    if case == "no_multiple_of_128":
        return rng.standard_normal(1000).astype(np.float32), 37
    if case == "ties_across_a_row_edge":
        # the k-th magnitude 24 times over, in rows 0 to 2: nine of
        # them leave, row 0's four and the first five of row 1's six
        x = (rng.random(700) * 0.5).astype(np.float32)
        at = np.r_[3, 50, 90, 127, 128, 129, 130, 200, 254, 255, 256,
                   257:270]
        x[at] = -0.75
        x[[5, 300, 699]] = [2.0, -3.0, 1.0]
        return x, 3 + 9
    if case == "ties_end_with_their_row":
        x = np.zeros(384, np.float32)
        x[[1, 100, 127, 128, 383]] = [1.0, -1.0, 1.0, 1.0, -1.0]
        return x, 3
    if case == "fewer_than_k_nonzeros":
        x = np.zeros(300, np.float32)
        x[[7, 130, 299]] = [0.5, -2.0, 1e-30]
        return x, 9
    if case == "negative_zeros":
        x = np.where(rng.random(260) < 0.5, -0.0, 0.0).astype(np.float32)
        x[[4, 259]] = [-1.0, 1.0]
        return x, 140
    if case == "k_is_1":
        return rng.standard_normal(513).astype(np.float32), 1
    if case == "k_is_n":
        return (np.round(rng.standard_normal(200)) / 2).astype(np.float32), 200
    if case == "a_tail_of_one":
        return rng.standard_normal(129).astype(np.float32), 64
    raise ValueError(case)


@pytest.mark.parametrize("side_by_side", [True, False])
@pytest.mark.parametrize("case", [
    "no_multiple_of_128", "ties_across_a_row_edge",
    "ties_end_with_their_row", "fewer_than_k_nonzeros", "negative_zeros",
    "k_is_1", "k_is_n", "a_tail_of_one"])
def test_the_rule_is_the_mask_of_the_returned_positions(case, side_by_side,
                                                        monkeypatch):
    """Three keys of one size in a flat vector, through both selection
    paths (side by side under ``vmap``, one after the other under
    ``lax.map``): ``leaving`` of every key's ``(t, cut)`` marks the
    positions ``topk_flat`` returned for it and nothing else."""
    if not side_by_side:
        monkeypatch.setattr(select, "_SIDE_BY_SIDE_ELEMS", 0)
    x, k = _rule_case(case)
    n = len(x)
    # the key itself, the key backwards and the key shifted by one:
    # three different cuts under one traced body
    keys = [x, x[::-1], np.roll(x, 1)]
    offsets = [3, 3 + n + 2, 3 + 2 * n + 9]
    v = np.full(offsets[-1] + n + 4, 9.0, np.float32)
    for o, key in zip(offsets, keys):
        v[o:o + n] = key

    def run(v):
        idx, vals, rules = select.topk_flat(v, offsets, [n] * 3, [k] * 3)
        (members, t, cut), = rules      # one size: one group
        assert members == [0, 1, 2]
        masks = [select.leaving(v[o:o + n], t[g], cut[g])
                 for g, o in enumerate(offsets)]
        return idx, vals, jnp.stack(masks)

    idx, vals, masks = map(np.asarray, jax.jit(run)(jnp.asarray(v)))
    for g, (o, key) in enumerate(zip(offsets, keys)):
        want = np.sort(np.argsort(-np.abs(key), kind="stable")[:k])
        np.testing.assert_array_equal(idx[g * k:(g + 1) * k] - o, want)
        np.testing.assert_array_equal(np.flatnonzero(masks[g]), want)
    np.testing.assert_array_equal(vals.view(np.int32),
                                  v[idx].view(np.int32))


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
    got, vals = map(np.asarray, fn(v)[:2])
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
        v, offsets, sizes, ks)[:2])(v))
    want = np.concatenate([
        np.sort(np.asarray(jax.lax.top_k(jnp.abs(v[o:o + s]), k)[1])) + o
        for o, s, k in zip(offsets, sizes, ks)])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(vals, np.asarray(v)[want])
    assert np.all(np.diff(got) > 0), "keys in flat order: all ascending"
