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


# -- the compaction's kernel form ---------------------------------------------

# a tile of 2 windows (256 rows, 32,768 elements), a part of 8 pieces of
# 128 slots: many tiles, parts and windows at sizes an interpreted
# kernel walks in a second
SMALL = (128, 2, 8)
WINDOW, TILE, PART = 128 * 128, 2 * 128 * 128, 8 * 128


def _odd_values(kind: str, n: int) -> np.ndarray:
    """The kinds ``_values`` has, and three the kernel has to move bit
    for bit: ties that straddle a window's and a tile's edge, every
    special image among the largest, and denormals with zeros of both
    signs at the k-th magnitude."""
    rng = np.random.default_rng(n + 1)
    if kind == "ties_at_the_edges":
        # one magnitude at every position near a window's or a tile's
        # first row, of both signs: the cut falls among them
        x = (rng.random(n) * 0.25).astype(np.float32)
        for edge in range(WINDOW, n, WINDOW):
            x[max(edge - 70, 0):edge + 70] = -0.75
        x[::2] = np.abs(x[::2])
        return x
    if kind == "specials":
        x = rng.standard_normal(n).astype(np.float32)
        at = rng.choice(n, min(n, 8), replace=False)
        x[at] = np.resize(np.array(
            [np.inf, -np.inf, np.nan, -np.nan, 3e38, -3e38], np.float32),
            len(at))
        return x
    if kind == "denormals":
        x = np.where(rng.random(n) < 0.5, -0.0, 0.0).astype(np.float32)
        live = rng.random(n) < 0.02
        x[live] = (rng.integers(1, 1 << 22, live.sum()).astype(np.int32)
                   | (rng.integers(0, 2, live.sum()).astype(np.int32) << 31)
                   ).view(np.float32)
        return x
    return _values(kind, n)


def _both_forms(x, k, monkeypatch, geometry=SMALL):
    """(kernel form, XLA's form) of one key, every result as integers;
    the kernel interpreted (this backend) at ``geometry``."""
    def images(out):
        return [np.asarray(a).view(np.int32) for a in out]

    monkeypatch.setattr(select, "GEOMETRY", geometry)
    return (images(jax.jit(
        lambda x: select.topk_by_magnitude(x, k, kernel=True))(x)),
            images(jax.jit(lambda x: select.topk_by_magnitude(x, k))(x)))


@pytest.mark.parametrize("threshold", [0.01, 0.3])
@pytest.mark.parametrize("kind", [
    "normal", "ties", "zeros", "equal", "infinity", "ties_at_the_edges",
    "specials", "denormals"])
@pytest.mark.parametrize("n", [
    100,                # under a row
    WINDOW - 1,         # a row short of a window
    WINDOW + 129,       # a window, a row and a lane
    TILE,               # a tile exactly
    TILE + WINDOW + 5,  # off a tile, off a window, off a row
    5 * TILE - 128 * 3 + 77,
])
def test_the_kernel_form_is_xlas_form_bit_for_bit(n, kind, threshold,
                                                   monkeypatch):
    """Positions, values, ``t`` and ``cut`` of both forms of the
    compaction (the kernel interpreted, at a small geometry), for sizes
    that end inside a window, a tile and a piece."""
    k = max(int(n * threshold), 1)
    x = jnp.asarray(_odd_values(kind, n))
    got, want = _both_forms(x, k, monkeypatch)
    for a, b, what in zip(got, want, ("positions", "values", "t", "cut")):
        np.testing.assert_array_equal(a, b, err_msg=what)
    assert got[0].shape == (k,) and np.all(np.diff(got[0]) > 0)
    np.testing.assert_array_equal(
        got[1], np.asarray(x).view(np.int32)[got[0]])


@pytest.mark.parametrize("n,k", [
    (select.GEOMETRY[1] * WINDOW + 12_345, 9_000),  # a tile and a bit
    (70_000, 700), (3 * select.GEOMETRY[1] * WINDOW, 40)])
def test_the_shipped_geometry(n, k, monkeypatch):
    x = jnp.asarray(_odd_values("ties", n))
    got, want = _both_forms(x, k, monkeypatch, select.GEOMETRY)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("where", ["spread", "one tile", "the last rows",
                                   "every element"])
def test_the_steps_table_covers_every_tile_and_part(where):
    """A tile's steps take consecutive parts, from the part the tile
    before ended on; every part that holds a slot of a tile's rows is
    among that tile's; the table's length is tiles + parts - 1."""
    piece, group, chunk = SMALL
    tile_rows, rows = group * 128, 11 * group * 128 - 50
    rng = np.random.default_rng(len(where))
    count = np.zeros(rows, np.int64)
    if where == "spread":
        count = (rng.random(rows) < 0.7) * rng.integers(0, 9, rows)
    elif where == "one tile":
        count[3 * tile_rows + 5:4 * tile_rows - 9] = 20
    elif where == "the last rows":
        count[-40:] = 128
    else:
        count[:] = 128
    k = int(count.sum())
    ntiles, nparts = -(-rows // tile_rows), -(-k // (chunk * piece))
    first = np.concatenate([[0], np.cumsum(count)])
    tile_slot = np.minimum(first[np.arange(ntiles) * tile_rows], k)
    tile_of = np.asarray(select._steps(
        jnp.asarray(tile_slot, jnp.int32), ntiles, nparts, chunk * piece))
    assert tile_of.shape == (ntiles + nparts - 1,)
    assert (np.diff(tile_of) >= 0).all()
    assert set(tile_of.tolist()) == set(range(ntiles))
    part_of = np.arange(len(tile_of)) - tile_of
    assert (np.diff(part_of) >= 0).all()
    assert set(part_of.tolist()) == set(range(nparts))
    met = set(zip(tile_of.tolist(), part_of.tolist()))
    slot_row = np.repeat(np.arange(rows), count)
    for s in range(0, k, 17):
        assert (slot_row[s] // tile_rows, s // (chunk * piece)) in met, s


# -- the rule -----------------------------------------------------------------

def _flat(total):
    return jax.ShapeDtypeStruct((total,), jnp.float32)


def test_the_rule_off_the_chip_is_xlas_form():
    from geomx_tpu import ops

    size = select.SELECT_MIN_ELEMS
    assert ops.pallas_interpret()
    assert not select.runs_kernel(_flat(8 * size), size)
    assert select.runs_kernel(_flat(8 * size), size, forced=True)
    assert select.kernel_keys(_flat(8 * size), [size] * 8, [9] * 8) == []


def test_the_rule_where_pallas_compiles(monkeypatch):
    """A TPU backend stood in for: the kernel from ``SELECT_MIN_ELEMS``
    elements, never under a mesh, whoever shows it; every size group
    selected one key after the other, never one selected side by
    side."""
    from geomx_tpu import ops

    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    size = select.SELECT_MIN_ELEMS
    v = _flat(64 * size)
    assert select.runs_kernel(v, size) and not select.runs_kernel(v, size - 1)
    mesh = jax.make_mesh((2,), ("dp",))
    assert not select.runs_kernel(v, size, mesh)
    with jax.set_mesh(mesh):
        assert not select.runs_kernel(v, size)
    # eight keys of the least size lie side by side; nine do not
    assert 8 * size <= select._SIDE_BY_SIDE_ELEMS < 9 * size
    assert select.kernel_keys(v, [size] * 8, [9] * 8) == []
    assert select.kernel_keys(v, [size] * 9, [9] * 9) == list(range(9))
    assert select.kernel_keys(v, [size] * 9, [9] * 9, mesh) == []
    # three large groups, a group of keys under the least size and one
    # that lies side by side
    sizes = [2 * size] * 8 + [size - 128] * 9 + [9 * size] + [300] * 5 + \
        [2 * size + 128] * 5
    ks = [max(s // 100, 1) for s in sizes]
    assert select.kernel_keys(v, sizes, ks) == \
        list(range(8)) + [17] + list(range(23, 28))
    # cell 1's keys (PERF.md section 4): every weight matrix but the
    # position embedding, 99.4% of its elements; the biases and norms
    # and the position embedding lie side by side
    gpt2 = [38_597_376] * 2 + [2_359_296] * 24 + [1_769_472] * 12 + \
        [589_824] * 12 + [786_432] + [768] * 74
    taken = select.kernel_keys(_flat(sum(gpt2)), gpt2,
                               [max(s // 100, 1) for s in gpt2])
    assert taken == list(range(50))


def test_topk_flat_takes_the_form_the_rule_gives(monkeypatch):
    """Forced, the keys selected one after the other compact by the
    kernel (interpreted here) and the keys selected side by side by
    XLA's form; the results are the unforced ones bit for bit."""
    from functools import partial

    monkeypatch.setattr(select, "_SIDE_BY_SIDE_ELEMS", 4000)
    sizes = [3000, 700, 3000, 129, 700, 5000, 700]
    ks = [max(int(s * 0.05), 1) for s in sizes]
    offsets = (np.concatenate([[0], np.cumsum(sizes)])[:-1] + 5).tolist()
    rng = np.random.default_rng(2)
    v = jnp.asarray(np.round(rng.standard_normal(sum(sizes) + 9) * 4) / 4,
                    jnp.float32)

    def flat(v):
        idx, vals, rules = select.topk_flat(v, offsets, sizes, ks)
        return idx, vals, [(t, cut) for _m, t, cut in rules]

    def traced():
        return str(jax.make_jaxpr(lambda v: flat(v))(v))

    want = jax.jit(lambda v: flat(v))(v)
    assert "pallas_call" not in traced()
    monkeypatch.setattr(select, "runs_kernel",
                        partial(select.runs_kernel, forced=True))
    # 2 x 3000 and 1 x 5000 are over the side-by-side bound; 3 x 700 and
    # 129 are under it
    assert select.kernel_keys(v, sizes, ks) == [0, 2, 5]
    assert traced().count("pallas_call") == 2
    got = jax.jit(lambda v: flat(v))(v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a).view(np.int32),
                                      np.asarray(b).view(np.int32))


def test_the_kernel_lowers_to_one_mosaic_call():
    """For a TPU, with no chip and no libtpu: one ``tpu_custom_call``,
    no gather or scatter of the key's or the list's size, no sort."""
    rows, k = 301_542, 385_973
    text = select._compactor(rows, k, False, select.GEOMETRY).trace(
        jax.ShapeDtypeStruct((rows * 128,), jnp.float32),
        jax.ShapeDtypeStruct((rows,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1, text[:2000]
    assert "stablehlo.sort" not in text
    assert '"stablehlo.scatter"' not in text
    moved = [line for line in text.splitlines()
             if '"stablehlo.gather"' in line
             and (f"{k}x" in line or f"{rows}x128" in line)]
    assert not moved, moved


def test_a_small_trainers_step_holds_the_budgeted_mosaic_calls(monkeypatch):
    """Set-up follows a program's text: a trainer whose keys come in
    four sizes over the side-by-side bound lowers, for a TPU, a fused
    step with one Mosaic call a size for the selection (the kernel's
    own ``jit`` is lowered once a shape, whatever the number of keys),
    and books the kernel's keys a round."""
    from functools import partial
    from types import SimpleNamespace

    from geomx_tpu import ops, telemetry
    from geomx_tpu.kvstore import create as kv_create
    from geomx_tpu.trainer_device import DeviceResidentTrainer

    monkeypatch.setattr(select, "_SIDE_BY_SIDE_ELEMS", 500)
    monkeypatch.setattr(select, "SELECT_MIN_ELEMS", 300)
    shapes = [(40, 16), (650,), (7,), (40, 16), (3, 100), (900,), (3, 100),
              (129,), (650,)]

    def grad_fn(leaves, x, _y):
        loss = sum(jnp.sum((leaf - x) ** 2) for leaf in leaves)
        return loss, [2 * (leaf - x) for leaf in leaves]

    def trainer():
        kv = kv_create("local")
        kv.cfg = SimpleNamespace(wire_codec="", p3_slice_bytes=0)
        return DeviceResidentTrainer(
            [np.zeros(s, np.float32) for s in shapes], kv, grad_fn,
            threshold=0.05, learning_rate=0.1, momentum=0.9)

    def step_text(tr):
        return tr._fwd_chunks.trace(
            tr._flat, tr._u, tr._v, jnp.asarray(0.5), None).lower(
                lowering_platforms=("tpu",)).as_text()

    plain = trainer()
    assert plain._select_kernel_keys == 0
    assert "tpu_custom_call" not in step_text(plain)
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    tr = trainer()
    # 2 x 650, 2 x 640, 900 and 2 x 300 are selected one after the
    # other; 7 and 129 side by side
    assert tr._select_kernel_keys == 7
    assert step_text(tr).count("tpu_custom_call") == 4
    # booked a round, interpreted here
    monkeypatch.undo()
    monkeypatch.setattr(select, "_SIDE_BY_SIDE_ELEMS", 500)
    monkeypatch.setattr(select, "runs_kernel",
                        partial(select.runs_kernel, forced=True))
    tr = trainer()
    was_on = telemetry.enabled()
    telemetry.enable(True)
    try:
        before = telemetry.snapshot()["counters"].get(
            "step.select_kernel_keys", 0)
        want = plain.step(jnp.asarray(0.5), None)
        got = tr.step(jnp.asarray(0.5), None)
        assert telemetry.snapshot()["counters"][
            "step.select_kernel_keys"] - before == tr._select_kernel_keys > 0
    finally:
        telemetry.enable(was_on)
    assert got == want
    for a, b in ((tr._flat, plain._flat), (tr._u, plain._u),
                 (tr._v, plain._v)):
        np.testing.assert_array_equal(np.asarray(a).view(np.int32),
                                      np.asarray(b).view(np.int32))
