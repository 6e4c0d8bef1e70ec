"""Quantized mesh collectives (GEOMX_MESH_CODEC): ring vs numpy oracle.

The tentpole claim (docs/mesh-party.md, quantized section): moving the
party's intra-mesh all-reduce from the fp32 GSPMD psum onto the
block-scaled ppermute ring changes the BYTES each hop moves, not the
replica coherence — and the device program is auditable bit-for-bit
against a host replay. These tests pin that down on the 8-virtual-device
CPU mesh (tests/conftest.py):

- **oracle bit-exactness**: for every codec the jitted shard_map ring
  must EQUAL a pure-numpy replay of the same schedule — quantize ->
  ppermute -> dequantize -> add per hop, residual slots carried across
  rounds. Exactness is by construction: int8 block scales are powers of
  two (quantize divide and dequant multiply are exact in f32, so LLVM's
  FMA contraction cannot perturb bits), 2-bit moves only {0, +thr, -thr}
  and fp16 narrowing is correctly-rounded — every wire value and every
  partial sum is reproducible on the host operation for operation.
- **"none" is the psum**: the codec-off build of the same collective is
  bitwise the GSPMD psum reference (the PR-8 path, untouched).
- **telemetry**: ring bytes land under ``mesh.bytes{codec=...}``,
  summed by mesh_bytes()/mesh_bytes_by_codec() and invisible to
  wan_bytes() — the WAN gate cannot absorb intra-DC traffic.
- **end-to-end replicas**: both trainers (DeviceResidentTrainer's fused
  step, HierarchicalTrainer's per-key reducers) keep parties
  bit-identical through quantized rounds — the all-gather phase relays
  the owner's codes verbatim, so every rank dequantizes the same bytes.
"""

import functools

import numpy as np
import pytest

from geomx_tpu import telemetry
from geomx_tpu.compression import device as dev
from geomx_tpu.parallel import quant_collectives as qc
from geomx_tpu.parallel.mesh import ring_chunk_layout

# -- numpy oracle ----------------------------------------------------------


def _np_quant(codec, e, res, block, thr):
    """Host twin of _HopCodec.quantize: (wire, deq, new_residual)."""
    if codec == "2bit":
        r = (res + e).astype(np.float32)
        t = np.float32(thr)
        pos = r > t
        neg = r < -t
        codes = np.where(pos, 1, np.where(neg, 2, 0)).astype(np.uint8)
        r = np.where(pos, r - t, np.where(neg, r + t, r)).astype(np.float32)
        c = codes.reshape(-1, 4)
        packed = (c[:, 0] | (c[:, 1] << 2) | (c[:, 2] << 4)
                  | (c[:, 3] << 6)).astype(np.uint8)
        return (packed,), _np_deq(codec, (packed,), e.size, block, thr), r
    e = (e + res).astype(np.float32)
    if codec == "int8":
        codes, exps = dev.block_quant_int8_np(e, block)
        deq = dev.block_dequant_int8_np(codes, exps, block)
        return (codes, exps), deq, (e - deq).astype(np.float32)
    if codec == "fp16":
        half = e.astype(np.float16)
        deq = half.astype(np.float32)
        return (half,), deq, (e - deq).astype(np.float32)
    raise AssertionError(codec)


def _np_deq(codec, wire, m, block, thr):
    if codec == "2bit":
        p = wire[0]
        c = np.stack([p & 3, (p >> 2) & 3, (p >> 4) & 3, (p >> 6) & 3],
                     axis=1).reshape(-1)[:m]
        t = np.float32(thr)
        return np.where(c == 1, t, np.where(c == 2, -t, 0.0)
                        ).astype(np.float32)
    if codec == "int8":
        return dev.block_dequant_int8_np(wire[0], wire[1], block)
    if codec == "fp16":
        return wire[0].astype(np.float32)
    raise AssertionError(codec)


def _oracle_round(xs, res, codec, block, thr):
    """Replay ONE quantized ring all-reduce on the host: ``xs`` is the
    (P, n) stack of rank contributions, ``res`` the (P, S, m) residual
    state (mutated to the new state). Returns the (P, n) per-rank
    outputs — which the test asserts are all identical."""
    P, n = xs.shape
    m, padded = ring_chunk_layout(n, P, qc._codec_multiple(codec, block))
    chunks = np.zeros((P, padded), np.float32)
    chunks[:, :n] = xs
    chunks = chunks.reshape(P, P, m)

    send = [chunks[r][r].copy() for r in range(P)]
    for s in range(P - 1):
        q = [_np_quant(codec, send[r], res[r, s], block, thr)
             for r in range(P)]
        for r in range(P):
            res[r, s] = q[r][2]
        # ppermute r -> r+1: rank r receives rank (r-1)'s wire
        for r in range(P):
            deq_rx = _np_deq(codec, q[(r - 1) % P][0], m, block, thr)
            send[r] = (deq_rx + chunks[r][(r - s - 1) % P]
                       ).astype(np.float32)

    out = np.zeros((P, P, m), np.float32)
    q = [_np_quant(codec, send[r], res[r, P - 1], block, thr)
         for r in range(P)]
    cur = [q[r][0] for r in range(P)]
    for r in range(P):
        res[r, P - 1] = q[r][2]
        out[r][(r + 1) % P] = q[r][1]
    for t in range(P - 1):
        cur = [cur[(r - 1) % P] for r in range(P)]
        for r in range(P):
            out[r][(r - t) % P] = _np_deq(codec, cur[r], m, block, thr)
    return out.reshape(P, padded)[:, :n]


def _mesh(size):
    import jax
    from geomx_tpu.parallel.mesh import make_mesh

    devs = jax.devices()
    assert len(devs) >= size, "tests need the 8-device virtual CPU mesh"
    return make_mesh(devs[:size])


# -- oracle bit-exactness --------------------------------------------------


@pytest.mark.mesh
@pytest.mark.parametrize("codec", ["int8", "2bit", "fp16"])
def test_ring_bit_exact_vs_oracle(codec):
    """3 rounds x 4 ranks: the jitted ring == the numpy replay, bit for
    bit, with the error-feedback residual carried across rounds (so a
    drifting residual stream would surface as a round-2+ mismatch)."""
    P, n, block, thr = 4, 1000, 64, 0.5
    mesh = _mesh(P)
    red = qc.QuantRingReducer(mesh, codec, n, block=block, threshold=thr)
    res_np = qc.zero_residual(P, n, codec, block)
    rng = np.random.RandomState(3)
    for rnd in range(3):
        xs = rng.randn(P, n).astype(np.float32)
        got = np.asarray(red.reduce(xs))
        want = _oracle_round(xs, res_np, codec, block, thr)
        # the oracle's ranks must agree with each other (verbatim-relay
        # all-gather) AND with the device ring
        for r in range(1, P):
            np.testing.assert_array_equal(want[r], want[0])
        np.testing.assert_array_equal(
            got, want[0],
            err_msg=f"codec={codec} round={rnd} device ring != oracle")
        np.testing.assert_array_equal(np.asarray(red._res), res_np)


@pytest.mark.mesh
@pytest.mark.parametrize("codec", ["int8", "2bit", "fp16"])
@pytest.mark.parametrize("n", [7, 64, 513])
def test_ring_odd_sizes_bit_exact(codec, n):
    """P=2 with sizes that don't divide the ring (padding + block
    rounding in play) — still bit-exact vs the oracle."""
    P, block, thr = 2, 32, 0.25
    mesh = _mesh(P)
    red = qc.QuantRingReducer(mesh, codec, n, block=block, threshold=thr)
    res_np = qc.zero_residual(P, n, codec, block)
    rng = np.random.RandomState(n)
    xs = rng.randn(P, n).astype(np.float32)
    got = np.asarray(red.reduce(xs))
    want = _oracle_round(xs, res_np, codec, block, thr)
    np.testing.assert_array_equal(got, want[0])


@pytest.mark.mesh
def test_residual_feedback_carries_error():
    """The int8 residual streams are non-trivial (quantization error is
    actually banked, not dropped) and a reset() zeroes them."""
    P, n = 4, 256
    mesh = _mesh(P)
    red = qc.QuantRingReducer(mesh, "int8", n, block=64)
    xs = np.random.RandomState(0).randn(P, n).astype(np.float32)
    red.reduce(xs)
    assert float(np.abs(np.asarray(red._res)).sum()) > 0
    red.reset()
    assert float(np.abs(np.asarray(red._res)).sum()) == 0.0


@pytest.mark.mesh
def test_mean_divides_by_ranks():
    P, n = 4, 64
    mesh = _mesh(P)
    xs = np.random.RandomState(1).randn(P, n).astype(np.float32)
    rs = qc.QuantRingReducer(mesh, "fp16", n)
    rm = qc.QuantRingReducer(mesh, "fp16", n, mean=True)
    np.testing.assert_array_equal(np.asarray(rs.reduce(xs)) / P,
                                  np.asarray(rm.reduce(xs)))


# -- "none" == the PR-8 psum ----------------------------------------------


@pytest.mark.mesh
def test_none_codec_is_psum_bitwise():
    """codec="none" degrades to the plain GSPMD psum — bitwise equal to
    the reference psum program, residual passed through untouched."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from geomx_tpu.parallel.mesh import P as Spec

    P_, n = 4, 333
    mesh = _mesh(P_)
    red = qc.QuantRingReducer(mesh, "none", n)
    xs = np.random.RandomState(2).randn(P_, n).astype(np.float32)
    res0 = np.asarray(red._res).copy()
    got = np.asarray(red.reduce(xs))

    ref_fn = jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v[0], "dp"), mesh=mesh,
        in_specs=(Spec("dp"),), out_specs=Spec(), check_vma=False))
    ref = np.asarray(ref_fn(jax.device_put(
        jnp.asarray(xs), NamedSharding(mesh, Spec("dp")))))
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(np.asarray(red._res), res0)
    assert red.wire_bytes_per_round() == 2 * (P_ - 1) * 4 * n


# -- loss parity -----------------------------------------------------------

# gap in final loss allowed against the psum, from a first loss of about
# 1.0: int8 and fp16 only round; the 2-bit ring sends {0, +thr, -thr} a
# hop and keeps a noise ball (at the reducer's default threshold, 0.5;
# what other thresholds read on this problem: PERF.md section 7)
RING_PARITY_TOL = {"int8": 5e-4, "fp16": 5e-4, "2bit": 0.05}


@functools.lru_cache(maxsize=None)
def _ring_regression_loss(codec, rounds=200, d=512, n_samples=256,
                          lr=0.1, ranks=4):
    """Final loss of a linear regression whose gradient is the ring's
    mean of four ranks' shard gradients, SGD on the replicated output."""
    red = qc.QuantRingReducer(_mesh(ranks), codec, d, mean=True)
    w_true = (np.random.RandomState(7).randn(d)
              / np.sqrt(d)).astype(np.float32)
    X = np.random.RandomState(42).randn(n_samples, d).astype(np.float32)
    y = X @ w_true
    per = n_samples // ranks
    Xs, ys = X.reshape(ranks, per, d), y.reshape(ranks, per)
    w = np.zeros(d, np.float32)
    for _ in range(rounds):
        g = np.stack([(2.0 / per) * Xs[r].T @ (Xs[r] @ w - ys[r])
                      for r in range(ranks)]).astype(np.float32)
        w -= lr * np.asarray(red.reduce(g))
    r = X @ w - y
    return float(np.mean(r * r))


@pytest.mark.mesh
@pytest.mark.parametrize("codec", sorted(RING_PARITY_TOL))
def test_quantized_ring_reaches_the_psums_loss(codec):
    """What bit-exactness against the oracle cannot say: that training
    through the quantized ring ends where the fp32 psum's does."""
    ref = _ring_regression_loss("none")
    assert ref < 1e-6, f"the psum itself did not converge: {ref}"
    got = _ring_regression_loss(codec)
    assert got - ref <= RING_PARITY_TOL[codec], (codec, got, ref)


# -- byte models -----------------------------------------------------------


def test_ring_wire_bytes_hits_compression_gates():
    """The ISSUE's bench gates, from the honest byte model: int8 >=3.5x
    below the fp32 ring, 2bit >=14x (codes + sidecar counted)."""
    n, P = 1 << 16, 4
    fp32 = qc.ring_wire_bytes("none", n, P)
    assert fp32 == 2 * (P - 1) * 4 * n
    assert fp32 / qc.ring_wire_bytes("int8", n, P, block=256) >= 3.5
    assert fp32 / qc.ring_wire_bytes("2bit", n, P) >= 14.0
    assert fp32 / qc.ring_wire_bytes("fp16", n, P) >= 1.9
    assert qc.ring_wire_bytes("int8", n, 1) == 0   # single-rank ring


def test_mesh_wire_bytes_model():
    assert dev.mesh_wire_bytes("none", 1024, 256) == 4096
    assert dev.mesh_wire_bytes("int8", 1024, 256) == 1024 + 4
    assert dev.mesh_wire_bytes("2bit", 1024, 256) == 256 + 4
    assert dev.mesh_wire_bytes("fp16", 1024, 256) == 2048


# -- telemetry: codec label, WAN exclusion ---------------------------------


def test_count_collective_codec_label_and_wan_exclusion():
    """mesh.bytes carries codec= and stays out of wan_bytes(); the
    counted value is the ring's wire model, not the fp32 payload."""
    from types import SimpleNamespace

    from geomx_tpu.kvstore.mesh_party import KVStorePartyMesh, _ring_bytes

    was = telemetry.enabled()
    try:
        telemetry.reset()
        telemetry.enable(True)
        nbytes = 4096 * 4
        for codec in ("none", "int8"):
            shim = SimpleNamespace(mesh_codec=codec, party_size=4,
                                   mesh_block=256)
            KVStorePartyMesh.count_collective(shim, nbytes)
        snap = telemetry.snapshot()
    finally:
        telemetry.reset()
        telemetry.enable(was)

    by_codec = telemetry.mesh_bytes_by_codec(snap)
    assert by_codec["none"] == _ring_bytes(4, nbytes)
    assert by_codec["int8"] == qc.ring_wire_bytes("int8", 4096, 4, 256)
    assert by_codec["int8"] < by_codec["none"] / 3.5
    assert telemetry.mesh_bytes(snap) == sum(by_codec.values())
    assert telemetry.wan_bytes(snap) == 0.0
    for key in snap["counters"]:
        if key.startswith("mesh."):
            assert "tier=mesh" in key


# -- end-to-end: trainers over the quantized mesh --------------------------


BSC_DIM = 8
ROUNDS = 4
_rng = np.random.RandomState(21)
E2E_DATA = _rng.randint(-8, 9, size=(ROUNDS, 2, 2, BSC_DIM)
                        ).astype(np.float32) * 0.25


def _bsc_master_init(kv):
    kv.init(0, np.zeros(BSC_DIM, np.float32))
    kv.wait()


def _bsc_grad_fn(leaves, X, y):
    import jax.numpy as jnp

    w = leaves[0]
    d = w[None, :] - X
    return 0.5 * jnp.mean(jnp.sum(d * d, axis=-1)), [jnp.mean(d, axis=0)]


def _run_device_trainer(codec):
    from geomx_tpu.simulate import InProcessHiPS
    from geomx_tpu.trainer_device import DeviceResidentTrainer

    sim = InProcessHiPS(num_parties=2, workers_per_party=2,
                        party_mesh_size=2,
                        extra_cfg={"mesh_codec": codec,
                                   "mesh_block": 4}).start()
    out = {}
    try:
        def worker(kv):
            p = sim.workers.index(kv)
            assert kv.mesh_codec == codec
            tr = DeviceResidentTrainer(
                [np.zeros(BSC_DIM, np.float32)], kv, _bsc_grad_fn,
                threshold=1.0, learning_rate=0.25)
            assert tr._mesh_quant == (codec != "none")
            for r in range(ROUNDS):
                tr.step(E2E_DATA[r, p].reshape(2, BSC_DIM), None)
            out[p] = np.array(tr.leaves[0])

        sim.run_workers(worker, include_master=_bsc_master_init,
                        timeout=300)
    finally:
        sim.stop()
    return out


@pytest.mark.mesh
def test_device_trainer_int8_replicas_identical():
    """DeviceResidentTrainer with the int8 ring fused into its jitted
    step: both parties end on the SAME bits (verbatim-relay all-gather
    keeps every rank's dequantized aggregate identical), and the
    quantized run's weights track the unquantized run."""
    mesh = _run_device_trainer("int8")
    np.testing.assert_array_equal(mesh[0], mesh[1])
    assert np.any(mesh[0] != 0)
    none = _run_device_trainer("none")
    np.testing.assert_array_equal(none[0], none[1])
    # block-scaled int8 with error feedback stays close to fp32
    assert float(np.max(np.abs(mesh[0] - none[0]))) < 0.05


@pytest.mark.mesh
def test_hierarchical_trainer_int8_parties_identical():
    """HierarchicalTrainer routes per-key grads through the store's
    ring reducers (kv.ring_reducer) instead of the XLA psum; parties
    stay bit-identical and the loss still falls."""
    import jax
    import jax.numpy as jnp
    import optax

    from geomx_tpu.models import MLP
    from geomx_tpu.optimizer import SGD
    from geomx_tpu.parallel.train_step import (DataParallelTrainer,
                                               HierarchicalTrainer)
    from geomx_tpu.simulate import InProcessHiPS

    def master_init(kv):
        model = MLP(features=(16, 4))
        params = model.init(jax.random.PRNGKey(42),
                            jnp.zeros((1, 8), jnp.float32))
        for i, leaf in enumerate(jax.tree_util.tree_leaves(params)):
            kv.init(i, np.asarray(leaf))
        kv.wait()

    sim = InProcessHiPS(num_parties=2, workers_per_party=2,
                        party_mesh_size=2,
                        extra_cfg={"mesh_codec": "int8",
                                   "mesh_block": 8}).start()
    out = {}
    try:
        sim.master.set_optimizer(SGD(learning_rate=0.1))

        def worker(kv):
            p = sim.workers.index(kv)
            model = MLP(features=(16, 4))
            dp = DataParallelTrainer(model, optax.sgd(0.1), kv.mesh,
                                     jnp.zeros((1, 8), jnp.float32),
                                     num_classes=4)
            ht = HierarchicalTrainer(dp, kv)
            ht.init_on_kvstore()
            rng = np.random.RandomState(0)
            X = rng.randn(8, 8).astype(np.float32)
            y = rng.randint(0, 4, (8,))
            losses = [ht.step(X, y) for _ in range(3)]
            leaves = jax.tree_util.tree_leaves(ht.t.params)
            out[p] = (np.concatenate([np.asarray(l).ravel()
                                      for l in leaves]), losses)

        sim.run_workers(worker, include_master=master_init, timeout=300)
    finally:
        sim.stop()

    w0, l0 = out[0]
    w1, _l1 = out[1]
    np.testing.assert_array_equal(w0, w1)
    assert l0[-1] < l0[0]
