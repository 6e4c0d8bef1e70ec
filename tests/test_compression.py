"""Unit tests for the WAN compression kernels (BSC / FP16 / 2-bit / MPQ).

Mirrors the reference's compression semantics (gradient_compression.cc):
momentum-corrected top-k with residual reset for BSC, residual-feedback
2-bit quantization, size-threshold routing for MPQ.
"""

import logging
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from geomx_tpu import kernels_native
from geomx_tpu.compression import (
    BSCCompressor,
    FP16Compressor,
    MPQCompressor,
    Pairs,
    _generic_decompress,
    TwoBitCompressor,
    bsc_compress,
    bsc_decompress,
    bsc_pull_compress,
    bsc_sample_boundary,
    bsc_sample_positions,
    draw_ahead,
    make_compressor,
    takes_pairs,
    two_bit_dequantize,
    two_bit_quantize,
)


def test_bsc_full_threshold_is_lossless_for_uniform_magnitudes():
    n = 1000
    grad = np.full(n, 0.5, dtype=np.float32)
    u = np.zeros(n, np.float32)
    v = np.zeros(n, np.float32)
    values, indices = bsc_compress(grad, u, v, threshold=1.0)
    assert values.size == n
    out = bsc_decompress(values, indices, n)
    np.testing.assert_allclose(out, grad, rtol=1e-6)
    # residual reset: transmitted coordinates zeroed
    assert np.all(v[indices] == 0) and np.all(u[indices] == 0)


def test_bsc_sparsifies_and_accumulates_residual():
    rng = np.random.default_rng(0)
    n = 10000
    grad = rng.normal(size=n).astype(np.float32)
    u = np.zeros(n, np.float32)
    v = np.zeros(n, np.float32)
    values, indices = bsc_compress(grad.copy(), u, v, threshold=0.01)
    # at most threshold * n entries transmitted (reference zipped_size cap)
    assert values.size <= int(n * 0.01)
    # untransmitted residual survives in v for the next round
    untouched = np.setdiff1d(np.arange(n), indices)
    assert np.count_nonzero(v[untouched]) > 0
    # transmitted values are the momentum-corrected v, largest magnitudes
    assert np.min(np.abs(values)) > 0


def test_bsc_momentum_correction_matches_reference_recurrence():
    # u = 0.9u + g ; v = v + u (reference: gradient_compression.cc:219-222)
    n = 100
    u = np.zeros(n, np.float32)
    v = np.zeros(n, np.float32)
    g1 = np.ones(n, np.float32)
    bsc_compress(g1, u, v, threshold=1.0)  # round 1: v = 1 -> all sent, reset
    assert np.all(v == 0)
    g2 = np.ones(n, np.float32)
    values, _ = bsc_compress(g2, u, v, threshold=1.0)
    # after reset u==0: u = 0*0.9+1 = 1, v = 0+1 = 1
    np.testing.assert_allclose(values, np.ones(n), rtol=1e-6)


@pytest.mark.parametrize("threshold", [0.01, 0.1])
@pytest.mark.parametrize("n", [1, 768, 2_000, 1_000_000])
def test_bsc_sample_boundary_draw(n, threshold):
    """The boundary sample: distinct positions in range, 0.5% of n but at
    least ceil(10/threshold) and never more than n (reference:
    gradient_compression.cc:203-212); and at a million elements the
    sampled boundary brackets the exact top-k one."""
    rng = np.random.default_rng(7)
    pos = bsc_sample_positions(n, threshold, rng)
    documented = min(max(int(n * 0.005), int(np.ceil(10 / threshold))), n)
    assert pos.size == documented
    assert np.unique(pos).size == pos.size
    assert pos.min() >= 0 and pos.max() < n
    if n == 1_000_000:
        v = np.random.default_rng(11).normal(size=n).astype(np.float32)
        boundary = bsc_sample_boundary(v, threshold, rng)
        mag = np.sort(np.abs(v))[::-1]
        k = int(n * threshold)
        assert mag[2 * k - 1] <= boundary <= mag[k // 2 - 1]


def test_bsc_compress_push_allocates_no_index_of_every_position():
    """Steady state (the key's u/v exist): the pass may not build an
    O(n) int64 array, nor dense float32 copies of the key. The
    permutation of all positions alone was 8n bytes."""
    import tracemalloc

    n = 4_000_000
    grad = np.random.default_rng(3).normal(size=n).astype(np.float32)
    gc = BSCCompressor(threshold=0.01)
    gc.compress_push(grad, "k")
    tracemalloc.start()
    try:
        start = tracemalloc.get_traced_memory()[0]
        values, indices, tag = gc.compress_push(grad, "k")
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert tag == "bsc" and 0 < values.size <= n // 100
    assert peak - start < 4 * n, (peak - start) / n


def _compressor_pair(n, seed):
    """Two Bi-Sparse compressors with one starting state for key "k":
    the same ``u`` and ``v`` (copies) and generators at the same point."""
    rng = np.random.default_rng(seed)
    u0 = rng.normal(size=n).astype(np.float32)
    v0 = rng.normal(size=n).astype(np.float32)
    pair = BSCCompressor(0.01), BSCCompressor(0.01)
    for gc in pair:
        gc._u["k"], gc._v["k"] = u0.copy(), v0.copy()
    return pair


def _same_state(a, b):
    for name in ("_u", "_v"):
        x, y = getattr(a, name)["k"], getattr(b, name)["k"]
        assert (x == y).all(), name


@pytest.mark.parametrize("wire", ["bsc", "bsc16"])
@pytest.mark.parametrize("order", ["magnitude", "sorted"])
@pytest.mark.parametrize("n", [1, 768, 1_000_000])
def test_bsc_compress_of_pairs_equals_the_dense_pass(n, order, wire):
    """Five rounds of a worker's selection (1% of the key, in the order
    ``lax.top_k`` hands it over or in index order), given to the pass as
    ``Pairs`` and as the array ``decompress_push`` builds: the same
    entries leave, and ``u`` and ``v`` are equal, every round."""
    dense, sparse = _compressor_pair(n, seed=n)
    rng = np.random.default_rng(n + 1)
    k = max(int(n * 0.01), 1)
    for _rnd in range(5):
        idx = rng.choice(n, k, replace=False).astype(np.int32)
        vals = rng.normal(size=k).astype(np.float32)
        by = np.argsort(-np.abs(vals)) if order == "magnitude" \
            else np.argsort(idx)
        idx = idx[by]
        vals = vals[by].astype(np.float16 if wire == "bsc16" else np.float32)
        want = dense.compress_push(_generic_decompress(wire, vals, idx, n),
                                   "k")
        got = sparse.compress_push(Pairs.from_wire(vals, idx, n), "k")
        assert got[2] == want[2] == "bsc"
        assert got[0].dtype == np.float32 and got[1].dtype == np.int32
        assert (got[0] == want[0]).all() and (got[1] == want[1]).all()
        _same_state(dense, sparse)


def test_bsc_compress_of_pairs_with_a_repeated_position():
    """A payload that repeats a position inside itself (no selection of
    this repo does): ``u`` gets its values one by one where the dense
    scatter summed them first, one unit of the last place apart at most,
    on that element alone."""
    n = 768
    dense, sparse = _compressor_pair(n, seed=5)
    idx = np.array([700, 3, 40, 3, 3], np.int32)
    vals = np.array([0.1, 0.3, -2.0, 1e-4, 0.7], np.float32)
    pairs = Pairs.from_wire(vals, idx, n)
    np.testing.assert_array_equal(pairs.dense(),
                                  _generic_decompress("bsc", vals, idx, n))
    u = sparse._u["k"].copy()
    u *= np.float32(0.9)
    pairs.add_into(u)
    want = dense._u["k"] * np.float32(0.9) + pairs.dense()
    others = np.arange(n) != 3
    assert (u[others] == want[others]).all()
    assert abs(u[3] - want[3]) <= np.spacing(np.abs(want[3]))
    # and through the pass: the same positions leave or stay
    a = dense.compress_push(pairs.dense(), "k")
    b = sparse.compress_push(pairs, "k")
    np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_allclose(a[0], b[0], rtol=2e-7, atol=0)


def test_bsc_compress_of_pairs_drops_out_of_range_positions(caplog):
    n = 768
    dense, sparse = _compressor_pair(n, seed=6)
    idx = np.array([5, n, 40, -1, 767], np.int32)
    vals = np.array([1.0, 9.0, -2.0, 9.0, 0.5], np.float32)
    with caplog.at_level(logging.WARNING, logger="geomx.compression"):
        arr = _generic_decompress("bsc", vals, idx, n)
        pairs = Pairs.from_wire(vals, idx, n)
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 2 and said[0] == said[1]
    assert "dropping 2 out-of-range indices" in said[0]
    np.testing.assert_array_equal(pairs.idx, [5, 40, 767])
    want = dense.compress_push(arr, "k")
    got = sparse.compress_push(pairs, "k")
    assert (got[0] == want[0]).all() and (got[1] == want[1]).all()
    _same_state(dense, sparse)


def test_pairs_cut_and_order():
    """``Pairs`` keep the wire's arrays and its order; a range is cut by
    a mask (no sort), ``entries()`` orders them and sums repeats in the
    order they came."""
    idx = np.array([9, 2, 5, 2], np.int32)
    vals = np.array([1.0, 2.0, 3.0, 0.5], np.float32)
    p = Pairs.from_wire(vals, idx, 10)
    assert np.shares_memory(p.idx, idx) and np.shares_memory(p.vals, vals)
    assert p[0:10] is p
    cut = p[2:9]
    np.testing.assert_array_equal(cut.idx, [0, 3, 0])
    np.testing.assert_array_equal(cut.vals, [2.0, 3.0, 0.5])
    assert cut.size == 7
    np.testing.assert_array_equal(cut.dense(), p.dense()[2:9])
    e = p.entries()
    np.testing.assert_array_equal(e.idx, [2, 5, 9])
    np.testing.assert_array_equal(e.vals, [2.5, 3.0, 1.0])
    assert e.entries() is e and e.idx.dtype == np.int32


@pytest.mark.parametrize("params,n,want", [
    ({"type": "bsc", "device": False}, 64, True),
    ({"type": "mpq", "device": False, "size_lower_bound": 1000}, 999, False),
    ({"type": "mpq", "device": False, "size_lower_bound": 1000}, 1000, True),
    ({"type": "bsc", "device": True}, 10_000, False),
    ({"type": "mpq", "device": True, "size_lower_bound": 1000}, 5000, False),
    ({"type": "fp16"}, 10_000, False),
    ({"type": "2bit"}, 10_000, False),
    (None, 10_000, False),
])
def test_takes_pairs_is_the_host_bsc_pass_alone(params, n, want):
    assert takes_pairs(make_compressor(params), n) is want


@pytest.mark.parametrize("params,n,want", [
    ({"type": "bsc", "device": False}, 64, True),
    ({"type": "mpq", "device": False, "size_lower_bound": 1000}, 999, False),
    ({"type": "mpq", "device": False, "size_lower_bound": 1000}, 1000, True),
    ({"type": "bsc", "device": True}, 10_000, False),
    ({"type": "fp16"}, 10_000, False),
    (None, 10_000, False),
])
def test_draw_ahead_is_for_the_host_bsc_pass_alone(params, n, want):
    """Positions where ``compress_push`` of such a key runs the host
    Bi-Sparse pass (``takes_pairs``), None for a compressor that keeps
    its draw, or has none, to itself."""
    gc = make_compressor(params)
    pos = draw_ahead(gc, n, ("k", 0))
    assert (pos is not None) is want is takes_pairs(gc, n)
    if want:
        ref = bsc_sample_positions(n, 0.01, np.random.default_rng(42))
        np.testing.assert_array_equal(pos, ref)


DRAWN_SIZES = [1, 40, 768, 2_000, 65_000, 300_000]


@pytest.mark.parametrize("order", ["key_order", "reversed", "threads_1",
                                   "threads_2", "threads_4"])
@pytest.mark.parametrize("kind", ["bsc", "mpq"])
def test_keys_drawn_ahead_compress_in_any_order(kind, order):
    """Five rounds of seeded ``Pairs`` on keys of mixed sizes: every
    key's sample drawn first, in key order, then the keys compressed
    in another order or side by side: values, positions, ``u``, ``v``
    and the generator's state are those of one ``compress_push`` a key
    in key order. MPQ's small keys go fp16 and draw nothing."""
    params = {"type": kind, "device": False, "size_lower_bound": 1000}
    serial, ahead = make_compressor(params), make_compressor(params)
    for rnd in range(5):
        rng = np.random.default_rng(50 + rnd)
        grads = []
        for n in DRAWN_SIZES:
            idx = rng.choice(n, max(n // 100, 1), replace=False)
            grads.append(Pairs(idx.astype(np.int32),
                               rng.standard_normal(idx.size).astype(
                                   np.float32), n))
        want = [serial.compress_push(
            g if takes_pairs(serial, g.size) else g.dense(), (i, 0))
            for i, g in enumerate(grads)]
        drawn = [draw_ahead(ahead, g.size, (i, 0))
                 for i, g in enumerate(grads)]

        def one(i):
            g = grads[i]
            if drawn[i] is None:
                return ahead.compress_push(g.dense(), (i, 0))
            return ahead.compress_push(g, (i, 0), positions=drawn[i])

        ids = range(len(grads))
        if order.startswith("threads"):
            with ThreadPoolExecutor(int(order[-1])) as pool:
                got = list(pool.map(one, sorted(ids, key=lambda i:
                                                -grads[i].size)))[::-1]
        elif order == "reversed":
            got = [one(i) for i in reversed(ids)][::-1]
        else:
            got = [one(i) for i in ids]
        for (wv, wi, wt), (gv, gi, gt) in zip(want, got):
            assert wt == gt and wv.dtype == gv.dtype
            np.testing.assert_array_equal(wv.view(np.uint8).ravel(),
                                          gv.view(np.uint8).ravel())
            if wi is None:
                assert gi is None
            else:
                np.testing.assert_array_equal(wi, gi)
    a, b = (gc._bsc if kind == "mpq" else gc for gc in (serial, ahead))
    assert list(a._u) == list(b._u)
    for k in a._u:
        assert a._u[k].tobytes() == b._u[k].tobytes()
        assert a._v[k].tobytes() == b._v[k].tobytes()
    assert a._rng.bit_generator.state == b._rng.bit_generator.state


# ---------------------------------------------------------------------------
# the Bi-Sparse pass as one native sweep (native/kernels.cc, gxk_bsc_*)
# against the numpy passes, which stay in the tree as its fallback


@pytest.fixture
def numpy_passes(monkeypatch):
    """-> a context in which ``bsc_compress`` runs its numpy passes."""
    import contextlib

    if kernels_native.lib() is None:
        pytest.skip("no native kernels here (g++)")

    @contextlib.contextmanager
    def off():
        with monkeypatch.context() as m:
            m.setattr(kernels_native, "bsc_pass_usable", lambda u, v: False)
            yield

    return off


def _sweep_case(case, n, rng):
    k = max(n // 100, 1)
    idx = rng.choice(n, k, replace=False).astype(np.int32)
    vals = rng.standard_normal(k).astype(np.float32)
    if case == "sorted":
        idx.sort()
    elif case == "repeated":        # a third of the positions twice
        idx[:k // 3] = idx[k // 3:2 * (k // 3)]
    elif case == "int64_edges":
        idx = np.sort(idx).astype(np.int64)
        idx[0], idx[-1] = 0, n - 1
    elif case == "nan_and_inf":
        vals[::7], vals[1::7] = np.nan, np.inf
    elif case == "no_pairs":
        idx, vals = idx[:0], vals[:0]
    return Pairs(idx, vals, n)


@pytest.mark.parametrize("case", ["sorted", "by_magnitude", "repeated",
                                  "int64_edges", "nan_and_inf", "no_pairs",
                                  "from_zero", "half_of_it"])
@pytest.mark.parametrize("n", [kernels_native.MIN_N, 16_392, 100_003,
                               1_000_000])
def test_one_sweep_is_the_numpy_passes_bit_for_bit(numpy_passes, case, n):
    """Four rounds a case: values, positions, ``u``, ``v`` and the
    generator after the sweep are those of the numpy passes, from a
    state of noise or of zeros (the first round's boundary of 0, where
    the cap cuts the selection), at 1% and at 50%, with positions
    sorted, as ``lax.top_k`` gives them, repeated, at both ends of the
    key, and with values that are not finite."""
    rng = np.random.default_rng(n % 1000 + len(case))
    threshold = 0.5 if case == "half_of_it" else 0.01
    if case in ("from_zero", "half_of_it"):
        u, v = np.zeros(n, np.float32), np.zeros(n, np.float32)
    else:
        u = (0.01 * rng.standard_normal(n)).astype(np.float32)
        v = rng.standard_normal(n).astype(np.float32)
        u[::11] = -0.0              # keeps its sign through the decay
    ru, rv = u.copy(), v.copy()
    gen, rgen = np.random.default_rng(42), np.random.default_rng(42)
    swept = []
    real = kernels_native.bsc_sweep

    def sweep(*args):
        swept.append(args[0].size)
        return real(*args)

    for _ in range(4):
        grad = _sweep_case(case, n, rng)
        kernels_native.bsc_sweep = sweep
        try:
            vals, idx = bsc_compress(grad, u, v, threshold, rng=gen)
        finally:
            kernels_native.bsc_sweep = real
        with numpy_passes():
            rvals, ridx = bsc_compress(grad, ru, rv, threshold, rng=rgen)
        assert vals.dtype == rvals.dtype == np.float32
        assert idx.dtype == ridx.dtype == np.int32
        assert vals.tobytes() == rvals.tobytes()
        assert idx.tobytes() == ridx.tobytes()
        assert u.tobytes() == ru.tobytes() and v.tobytes() == rv.tobytes()
        assert gen.bit_generator.state == rgen.bit_generator.state
    assert swept == [n] * 4         # the sweep ran, every round


@pytest.mark.parametrize("why", ["small_key", "strided_state",
                                 "read_only_state", "float64_state",
                                 "dense_gradient", "position_past_the_key"])
def test_the_sweep_is_passed_over_where_it_cannot_run(numpy_passes, why):
    """A key under ``MIN_N`` elements, a state the library cannot be
    handed, a dense gradient, pairs outside the key: the numpy passes
    run, with their result or their error, and the library is not
    called."""
    n = 2_000 if why == "small_key" else 20_000
    rng = np.random.default_rng(3)
    grad = _sweep_case("by_magnitude", n, rng)
    u = np.zeros(n, np.float32)
    v = rng.standard_normal(n).astype(np.float32)
    if why == "strided_state":
        u = np.zeros(2 * n, np.float32)[::2]
    elif why == "float64_state":
        u = np.zeros(n, np.float64)
    elif why == "dense_gradient":
        grad = grad.dense()
    elif why == "position_past_the_key":
        grad.idx[5] = n
    ru, rv = u.copy(), v.copy()
    if why == "read_only_state":
        v.setflags(write=False)

    def never(*args):
        raise AssertionError("the native sweep was called")

    pos = bsc_sample_positions(n, 0.01, rng)
    real = kernels_native.bsc_sweep
    kernels_native.bsc_sweep = never
    try:
        if why in ("read_only_state", "position_past_the_key"):
            with pytest.raises((ValueError, IndexError)):
                bsc_compress(grad, u, v, 0.01, positions=pos)
            return
        vals, idx = bsc_compress(grad, u, v, 0.01, positions=pos)
    finally:
        kernels_native.bsc_sweep = real
    with numpy_passes():
        rvals, ridx = bsc_compress(grad, ru, rv, 0.01, positions=pos)
    assert vals.tobytes() == rvals.tobytes()
    assert idx.tobytes() == ridx.tobytes()
    assert u.tobytes() == ru.tobytes() and v.tobytes() == rv.tobytes()


def test_bsc_pull_compress_keeps_nonzeros():
    arr = np.zeros(1000, np.float32)
    idx = np.array([3, 500, 999])
    arr[idx] = [1.5, -2.0, 0.25]
    values, indices = bsc_pull_compress(arr, threshold=0.01, multiplier=2)
    np.testing.assert_array_equal(np.sort(indices), idx)
    out = bsc_decompress(values, indices, 1000)
    np.testing.assert_allclose(out, arr)


def test_two_bit_roundtrip_with_residual():
    thr = 0.5
    grad = np.array([0.7, -0.6, 0.2, 0.0, 1.4], np.float32)
    residual = np.zeros(5, np.float32)
    packed = two_bit_quantize(grad.copy(), residual, thr)
    out = two_bit_dequantize(packed, 5, thr)
    np.testing.assert_allclose(out, [thr, -thr, 0, 0, thr])
    # residual carries the quantization error
    np.testing.assert_allclose(residual, [0.2, -0.1, 0.2, 0.0, 0.9], atol=1e-6)
    # second round drains the residual
    packed2 = two_bit_quantize(np.zeros(5, np.float32), residual, thr)
    out2 = two_bit_dequantize(packed2, 5, thr)
    np.testing.assert_allclose(out2, [0, 0, 0, 0, thr])


@pytest.mark.parametrize("n", [1, 2, 3, 5, 6, 7, 9])
def test_two_bit_roundtrip_length_not_divisible_by_4(n):
    """The pack pads to a whole byte (4 codes each); dequantize must
    honor original_size exactly — no truncation, no phantom tail codes."""
    thr = 0.25
    rng = np.random.default_rng(n)
    grad = rng.normal(scale=1.0, size=n).astype(np.float32)
    residual = np.zeros(n, np.float32)
    res_oracle = residual.copy()
    packed = two_bit_quantize(grad.copy(), residual, thr)
    assert packed.size == (n + 3) // 4 and packed.dtype == np.uint8
    out = two_bit_dequantize(packed, n, thr)
    assert out.size == n
    # element-wise oracle: code from the residual-fed value
    res_oracle += grad
    expect = np.where(res_oracle > thr, thr,
                      np.where(res_oracle < -thr, -thr, 0.0)
                      ).astype(np.float32)
    np.testing.assert_array_equal(out, expect)
    np.testing.assert_allclose(residual, res_oracle - expect, atol=1e-7)
    # pad codes beyond n must decode to nothing: a second dequantize at
    # the padded length shows zeros past the original size
    padded = two_bit_dequantize(packed, packed.size * 4, thr)
    np.testing.assert_array_equal(padded[n:], 0.0)


def test_mpq_size_lower_bound_boundary():
    """Routing at the MXNET_KVSTORE_SIZE_LOWER_BOUND boundary: exactly
    at the bound takes the large-tensor (BSC) route — the same
    inclusive convention the wire codec's chunk router uses."""
    bound = 100
    c = MPQCompressor(threshold=1.0, size_lower_bound=bound)
    for n, want in ((bound - 1, "fp16"), (bound, "bsc"),
                    (bound + 1, "bsc")):
        _, _, tag = c.compress_push(np.ones(n, np.float32), ("k", n))
        assert tag == want, (n, tag)
        assert c.push_tag(n) == want
    # pull side mirrors the route
    assert c.pull_compr_tag(bound - 1) == "fp16"
    assert c.pull_compr_tag(bound) == "bsc"


def test_fp16_wire_cast():
    c = FP16Compressor()
    arr = np.linspace(-3, 3, 77, dtype=np.float32)
    wire, aux, tag = c.compress_push(arr)
    assert wire.dtype == np.float16 and tag == "fp16"
    out = c.decompress_push(tag, wire, aux, arr.size)
    np.testing.assert_allclose(out, arr, atol=2e-3)


def test_mpq_routes_by_size():
    c = MPQCompressor(threshold=0.5, size_lower_bound=100)
    small = np.ones(10, np.float32)
    large = np.ones(1000, np.float32)
    _, _, tag_small = c.compress_push(small, ("k", 0))
    _, _, tag_large = c.compress_push(large, ("k2", 0))
    assert tag_small == "fp16"
    assert tag_large == "bsc"


def test_compressor_server_roundtrip_via_tags():
    """The exact pipeline the HiPS server runs on the WAN hop."""
    gc = BSCCompressor(threshold=1.0)
    grad = np.full(500, 0.25, np.float32)
    wire, aux, tag = gc.compress_push(grad, state_key=(0, 0))
    dense = gc.decompress_push(tag, wire, aux, 500)
    np.testing.assert_allclose(dense, grad)
    # pull side: aggregated (sparse) array, factor = num global workers
    payload, p_aux = gc.compress_pull("bsc", dense * 2, factor=2)
    back = gc.decompress_pull("bsc", payload, p_aux, 500, 2)
    np.testing.assert_allclose(back, grad * 2)


def test_make_compressor_factory():
    assert make_compressor(None).type_name == "none"
    assert make_compressor({"type": "bsc", "threshold": 0.02}).threshold == 0.02
    assert make_compressor({"type": "fp16"}).type_name == "fp16"
    assert make_compressor({"type": "mpq"}).type_name == "mpq"
    with pytest.raises(ValueError):
        make_compressor({"type": "wavelet"})


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
