"""Unit tests for the WAN compression kernels (BSC / FP16 / 2-bit / MPQ).

Mirrors the reference's compression semantics (gradient_compression.cc):
momentum-corrected top-k with residual reset for BSC, residual-feedback
2-bit quantization, size-threshold routing for MPQ.
"""

import numpy as np
import pytest

from geomx_tpu.compression import (
    BSCCompressor,
    FP16Compressor,
    MPQCompressor,
    TwoBitCompressor,
    bsc_compress,
    bsc_decompress,
    bsc_pull_compress,
    bsc_sample_boundary,
    bsc_sample_positions,
    make_compressor,
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
