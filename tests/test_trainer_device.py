"""DeviceResidentTrainer: device-resident params, BSC-compressed link.

Validates the cnn_bsc-style round (aggregator PS, worker-side optimizer)
over a LIVE two-party in-process HiPS topology: exactness at
threshold=1.0 (top-k covers everything -> must equal dense data-parallel
SGD), replica consistency, convergence at sparse thresholds, and the
compact-payload claim.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np

from geomx_tpu.simulate import InProcessHiPS
from geomx_tpu.trainer_device import DeviceResidentTrainer

TARGET = np.arange(1.0, 9.0, dtype=np.float32).reshape(2, 4)


def _grad_fn(leaves, X, y):
    """Quadratic bowl: loss = 0.5*||w - target||^2 (per worker batch
    shift given by X so worker grads differ)."""
    w = leaves[0]
    diff = w - jnp.asarray(TARGET) + X
    return 0.5 * jnp.sum(diff * diff), [diff]


def _run_two_workers(threshold, rounds=30, lr=0.2, momentum=0.0):
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    results = {}
    try:
        def worker(kv):
            widx = 0 if kv is topo.workers[0] else 1
            tr = DeviceResidentTrainer(
                [np.zeros((2, 4), np.float32)], kv, _grad_fn,
                threshold=threshold, learning_rate=lr, momentum=momentum)
            # worker batches pull in opposite directions; the MEAN grad
            # points at TARGET exactly
            shift = jnp.asarray(0.5 if widx == 0 else -0.5)
            for _ in range(rounds):
                tr.step(shift, None)
            results[widx] = tr.leaves[0]

        def master_init(kv):
            kv.init(0, np.zeros((2, 4), np.float32))
            kv.wait()

        t = threading.Thread(target=lambda: topo.run_workers(
            worker, include_master=master_init, timeout=300))
        t.start()
        t.join(300)
        assert not t.is_alive(), "workers hung"
    finally:
        topo.stop()
    return results


def test_dense_threshold_matches_plain_sgd():
    """threshold=1.0 selects every coordinate -> the distributed run
    must track plain full-gradient SGD on the mean gradient exactly
    (BSC with k=n is lossless)."""
    res = _run_two_workers(threshold=1.0, rounds=25, lr=0.2)
    w = np.zeros((2, 4), np.float32)
    for _ in range(25):
        w = w - 0.2 * (w - TARGET)  # mean of the two shifted grads
    np.testing.assert_allclose(res[0], w, rtol=1e-5, atol=1e-5)


def test_replicas_stay_identical():
    res = _run_two_workers(threshold=0.5, rounds=20)
    np.testing.assert_array_equal(res[0], res[1])


def test_sparse_threshold_converges():
    """With k=2 of 8 coords per round, the iterate lands in a bounded
    neighborhood of the optimum (BSC residual feedback batches deferred
    coordinates, so persistent worker dissent -> bounded oscillation,
    not exact convergence — reference behavior)."""
    res = _run_two_workers(threshold=0.25, rounds=150, lr=0.15)
    err = np.abs(res[0] - TARGET)
    assert float(err.mean()) < 0.25 and float(err.max()) < 0.6, res[0]


def test_momentum_variant_matches_heavyball():
    """threshold=1.0 makes the wire lossless, so the local momentum
    update must equal plain heavyball SGD on the mean gradient."""
    res = _run_two_workers(threshold=1.0, rounds=30, lr=0.05, momentum=0.9)
    w = np.zeros((2, 4), np.float32)
    mom = np.zeros_like(w)
    for _ in range(30):
        mom = 0.9 * mom + (w - TARGET)
        w = w - 0.05 * mom
    np.testing.assert_allclose(res[0], w, rtol=1e-5, atol=1e-5)


def test_payload_is_compact():
    """The device->host payload is k = ceil(total*threshold) pairs."""
    from geomx_tpu.kvstore import create as kv_create

    kv = kv_create("local")
    tr = DeviceResidentTrainer(
        [np.zeros((100,), np.float32)], kv, _grad_fn_100,
        threshold=0.02, learning_rate=0.1)
    assert tr.k == 2
    # and a local round still works end to end
    tr.step(jnp.asarray(0.0), None)
    assert tr.leaves[0].shape == (100,)


def _grad_fn_100(leaves, X, y):
    w = leaves[0]
    return 0.5 * jnp.sum(w * w), [w + 1.0]


def test_warmup_compiles_without_state_change():
    from geomx_tpu.kvstore import create as kv_create

    kv = kv_create("local")
    tr = DeviceResidentTrainer(
        [np.zeros((16,), np.float32)], kv, _grad_fn_16,
        threshold=0.5, learning_rate=0.1)
    before = tr.leaves[0].copy()
    tr.warmup(jnp.asarray(0.0), None)
    np.testing.assert_array_equal(tr.leaves[0], before)
    tr.step(jnp.asarray(0.0), None)  # and a real round still works
    assert not np.array_equal(tr.leaves[0], before)


def _grad_fn_16(leaves, X, y):
    w = leaves[0]
    return 0.5 * jnp.sum(w * w), [w + 1.0]


def test_packed_wire_is_int32_and_index_exact():
    """Round-4 chip regression: the packed device<->host payload must be
    an INT32 array (floats bitcast int-wards), never float32 with
    indices bitcast float-wards. Indices < 2^23 bitcast to float32 are
    denormals, and TPU float data movement inside jit flushes denormals
    to zero — on the first real-TPU capture every index collapsed to 0
    and headline accuracy fell to chance (0.0967).
    CPU can't reproduce the flush, so this asserts the wire CONTRACT:
    dtype int32 end-to-end and bit-exact recovery of small indices."""
    from geomx_tpu.kvstore import create as kv_create

    rng = np.random.default_rng(3)
    w = rng.standard_normal(500).astype(np.float32)

    def gfn(leaves, X, y):
        return jnp.sum(leaves[0]), [jnp.asarray(w)]

    kv = kv_create("local")
    tr = DeviceResidentTrainer([np.zeros(500, np.float32)], kv, gfn,
                               threshold=0.01, learning_rate=1.0)
    packed, _u, _v = tr._fwd_compress(tr._flat, tr._u, tr._v,
                                      jnp.asarray(0.0), None)
    assert np.asarray(packed).dtype == np.int32
    k = tr.k
    p = np.asarray(packed)
    idx = p[1 + k:]
    vals = p[1:1 + k].view(np.float32)
    # exact top-k of the rigged gradient: u=g, v=g -> top-|g| coords
    expect = np.argsort(-np.abs(w), kind="stable")[:k]
    assert set(idx.tolist()) == set(expect.tolist())
    np.testing.assert_array_equal(np.sort(np.abs(vals)),
                                  np.sort(np.abs(w[expect])))
