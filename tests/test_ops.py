"""Device compression kernels (geomx_tpu.ops) vs host numpy kernels.

Property tests: the device kernels must satisfy the same contracts as
geomx_tpu.compression's numpy implementations (which the HiPS protocol
tests already pin end-to-end), and where the device version is EXACT
top-k (vs the reference's sampled boundary) we assert exactness
directly."""

import numpy as np
import pytest

from geomx_tpu import compression as host
from geomx_tpu import ops


def test_bsc_compress_exact_topk_and_state():
    rng = np.random.default_rng(0)
    n, thr = 4096, 0.05
    grad = rng.normal(size=n).astype(np.float32)
    u = rng.normal(size=n).astype(np.float32)
    v = rng.normal(size=n).astype(np.float32)

    vals, idx, u2, v2 = ops.bsc_compress(grad, u.copy(), v.copy(), thr)
    vals, idx, u2, v2 = map(np.asarray, (vals, idx, u2, v2))
    k = int(n * thr)
    assert vals.shape == (k,) and idx.shape == (k,)

    # state recurrence matches the host kernel's definition
    u_ref = host.BSC_MOMENTUM * u + grad
    v_ref = v + u_ref
    # exact top-k of |v_ref|
    expect_idx = np.argsort(-np.abs(v_ref), kind="stable")[:k]
    assert set(np.abs(v_ref)[idx].round(5)) == \
        set(np.abs(v_ref)[expect_idx].round(5))
    np.testing.assert_allclose(vals, v_ref[idx], rtol=1e-5, atol=1e-6)
    # transmitted coordinates reset, others kept
    np.testing.assert_allclose(u2[idx], 0.0)
    np.testing.assert_allclose(v2[idx], 0.0)
    mask = np.ones(n, bool)
    mask[idx] = False
    np.testing.assert_allclose(v2[mask], v_ref[mask], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(u2[mask], u_ref[mask], rtol=1e-5, atol=1e-6)


def test_bsc_device_roundtrip_matches_host_decompress():
    rng = np.random.default_rng(1)
    n = 1000
    grad = rng.normal(size=n).astype(np.float32)
    u = np.zeros(n, np.float32)
    v = np.zeros(n, np.float32)
    vals, idx, _, _ = ops.bsc_compress(grad, u, v, 0.1)
    dense_dev = np.asarray(ops.bsc_decompress(np.asarray(vals),
                                              np.asarray(idx), n))
    dense_host = host.bsc_decompress(np.asarray(vals), np.asarray(idx), n)
    np.testing.assert_allclose(dense_dev, dense_host)
    # first round: v = grad, so selected values are gradient entries
    np.testing.assert_allclose(dense_dev[np.asarray(idx)],
                               grad[np.asarray(idx)], rtol=1e-5, atol=1e-6)


def test_bsc_pull_compress_captures_all_nonzeros():
    arr = np.zeros(512, np.float32)
    nz = np.random.default_rng(2).choice(512, 20, replace=False)
    arr[nz] = np.random.default_rng(3).normal(size=20).astype(np.float32)
    vals, idx = ops.bsc_pull_compress(arr, 0.05, 4)  # cap=102 >= 20
    back = np.asarray(ops.bsc_decompress(np.asarray(vals),
                                         np.asarray(idx), 512))
    np.testing.assert_allclose(back, arr, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [64, 1001])
def test_two_bit_matches_host_kernel(n):
    rng = np.random.default_rng(4)
    grad = rng.normal(size=n).astype(np.float32)
    residual = rng.normal(scale=0.3, size=n).astype(np.float32)
    thr = 0.5

    res_host = residual.copy()
    packed_host = host.two_bit_quantize(grad, res_host, thr)
    packed_dev, res_dev = ops.two_bit_quantize(grad, residual, thr)
    np.testing.assert_array_equal(np.asarray(packed_dev), packed_host)
    np.testing.assert_allclose(np.asarray(res_dev), res_host, rtol=1e-5, atol=1e-6)

    deq_dev = np.asarray(ops.two_bit_dequantize(np.asarray(packed_dev),
                                                n, thr))
    deq_host = host.two_bit_dequantize(packed_host, n, thr)
    np.testing.assert_allclose(deq_dev, deq_host)


def test_dgt_block_contrib_ewma():
    grad = np.arange(10, dtype=np.float32) - 5.0   # |g| known
    prev = np.zeros(3, np.float32)
    out = np.asarray(ops.dgt_block_contrib(grad, prev, 4, 0.25))
    m0 = np.abs(grad[0:4]).mean()
    m1 = np.abs(grad[4:8]).mean()
    m2 = np.abs(grad[8:10]).mean()   # padded tail: mean over TRUE elems
    np.testing.assert_allclose(out, 0.75 * np.array([m0, m1, m2]),
                               rtol=1e-5, atol=1e-6)
    out2 = np.asarray(ops.dgt_block_contrib(grad, out, 4, 0.25))
    np.testing.assert_allclose(
        out2, 0.25 * out + 0.75 * np.array([m0, m1, m2]), rtol=1e-5, atol=1e-6)


def test_device_bsc_compressor_end_to_end_topology():
    """The device compressor slots into the live HiPS WAN hop."""
    from tests.harness import Topology, _parallel

    topo = Topology().start(sync_global=True)
    try:
        topo.master.set_gradient_compression(
            {"type": "bsc", "threshold": 1.0, "device": True})
        w0 = np.full(64, 7.0, np.float32)
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in topo.workers + [topo.master]])

        def train(kv):
            kv.push(0, np.full(64, 0.25, np.float32))
            out = np.zeros(64, np.float32)
            kv.pull(0, out=out)
            kv.wait()
            np.testing.assert_allclose(out, np.full(64, 1.0), rtol=1e-5)

        _parallel([lambda kv=kv: train(kv) for kv in topo.workers])
    finally:
        topo.stop()


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
