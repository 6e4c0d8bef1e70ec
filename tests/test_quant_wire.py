"""Quantized combined wire: numpy-oracle bit-exactness.

The wire codec (compression.device.WireCodec, GEOMX_WIRE_CODEC) narrows
every leg of a combined round — worker push, party WAN forward, global
response, party response — to fp16 or residual-feedback 2-bit codes.
These tests replay the EXACT four-leg protocol chain in numpy (same
kernels, same residual streams, same aggregation order) and require the
live multi-node topology to reproduce it bit for bit, across >= 3
rounds so error-feedback residual carry is covered, on both the plain
van tier (``dist_sync``) and the mesh-party tier (``dist_sync_mesh``).
Aggregator mode throughout (no optimizer): the store holds the round's
aggregated gradient, so responses quantize too — both directions of
the WAN narrow, which is where the >= 4x byte drop comes from.
"""

import functools

import numpy as np
import pytest

from geomx_tpu import telemetry
from geomx_tpu.compression import two_bit_dequantize, two_bit_quantize
from geomx_tpu.compression.device import (WireCodec, codec_requires_aux,
                                          decode_wire)
from geomx_tpu.kvstore.frontier import plan_chunks
from geomx_tpu.simulate import InProcessHiPS

THR = 0.5          # wire_2bit_threshold (the config default)
KEYS = [0, 1, 2]
SIZES = [6, 9, 4]  # 6 and 9 exercise the 2-bit pad (not divisible by 4)
ROUNDS = 3


def _f16(x):
    return np.asarray(x, np.float32).astype(np.float16).astype(np.float32)


def _qd(x, res):
    """One quantize->dequantize hop: what the receiving node sees after
    a 2-bit leg, with ``res`` the sender's error-feedback residual
    (mutated in place, exactly like the wire's encode)."""
    packed = two_bit_quantize(np.asarray(x, np.float32).copy(), res, THR)
    return two_bit_dequantize(packed, x.size, THR)


def _g(widx, rnd, key, n):
    """Deterministic per-(worker, round, key) gradient."""
    rng = np.random.RandomState(1000 + 97 * widx + 13 * rnd + key)
    return rng.uniform(-1, 1, n).astype(np.float32)


# ---------------------------------------------------------------------------
# policy / planner units
# ---------------------------------------------------------------------------

def test_wire_codec_rejects_unknown_policy():
    with pytest.raises(ValueError, match="GEOMX_WIRE_CODEC"):
        WireCodec("zstd")


def test_codec_requires_aux():
    assert codec_requires_aux("2bit")
    assert codec_requires_aux("bsc16")
    assert codec_requires_aux("rsp")
    assert not codec_requires_aux("")
    assert not codec_requires_aux("fp16")
    assert not codec_requires_aux("bsc")


def test_chunk_codec_routing():
    assert WireCodec("").chunk_codec(0, 4, 10 ** 6) == ""
    assert WireCodec("fp16").chunk_codec(3, 4, 10 ** 6) == "fp16"
    assert WireCodec("2bit").chunk_codec(0, 4, 1) == "2bit"
    # MPQ: the size rule at chunk granularity — boundary is inclusive
    mpq = WireCodec("mpq", size_lower_bound=100)
    assert mpq.chunk_codec(0, 3, 99) == "fp16"
    assert mpq.chunk_codec(0, 3, 100) == "2bit"
    assert mpq.chunk_codec(0, 3, 101) == "2bit"
    # P3: the head chunk keeps fp16 no matter its size; tails route mpq
    p3 = WireCodec("p3", size_lower_bound=100)
    assert p3.chunk_codec(0, 3, 10 ** 6) == "fp16"
    assert p3.chunk_codec(1, 3, 100) == "2bit"
    assert p3.chunk_codec(2, 3, 99) == "fp16"


def test_plan_chunks_stamps_codec():
    mpq = WireCodec("mpq", size_lower_bound=8)
    # 4-elem chunk (16 bytes) then 16-elem chunk (64 bytes)
    chunks = plan_chunks(["a", "b"], [16, 64], 16,
                         codec_for=mpq.chunk_codec)
    assert [c.codec for c in chunks] == ["fp16", "2bit"]
    # zero budget: one chunk, codec from the round's total element count
    chunks = plan_chunks(["a", "b"], [16, 64], 0,
                         codec_for=mpq.chunk_codec)
    assert len(chunks) == 1 and chunks[0].codec == "2bit"
    # no codec_for: codec stays raw
    assert plan_chunks(["a"], [16], 0)[0].codec == ""


def test_encode_decode_2bit_residual_carry_vs_oracle():
    """Host encode path == the raw kernels, including residual carry
    across rounds and the non-divisible-by-4 pad."""
    wc = WireCodec("2bit", threshold=THR)
    res = np.zeros(7, np.float32)
    rng = np.random.RandomState(3)
    for _ in range(4):
        g = rng.uniform(-1, 1, 7).astype(np.float32)
        wv, aux, tag = wc.encode("2bit", g, ("k", 0))
        assert tag == "2bit" and wv.dtype == np.uint8 and wv.size == 2
        np.testing.assert_array_equal(aux, np.asarray([THR], np.float32))
        expect = _qd(g, res)
        np.testing.assert_array_equal(
            decode_wire("2bit", wv, aux, 7), expect)
    wc.reset(("k", 0))
    wv, aux, _ = wc.encode("2bit", np.ones(7, np.float32), ("k", 0))
    np.testing.assert_array_equal(
        decode_wire("2bit", wv, aux, 7),
        _qd(np.ones(7, np.float32), np.zeros(7, np.float32)))


def test_encode_fp16_and_raw():
    wc = WireCodec("fp16")
    g = np.asarray([1.0001, -2.5, 3e-5], np.float32)
    wv, aux, tag = wc.encode("fp16", g)
    assert tag == "fp16" and wv.dtype == np.float16 and aux is None
    np.testing.assert_array_equal(decode_wire("fp16", wv, None, 3), _f16(g))
    wv, aux, tag = wc.encode("", g)
    assert tag == "" and aux is None
    np.testing.assert_array_equal(decode_wire("", wv, None, 3), g)


# ---------------------------------------------------------------------------
# dense combined rounds vs the four-leg numpy oracle
# ---------------------------------------------------------------------------

def _run_dense_wire(policy, party_mesh_size=0, rounds=ROUNDS):
    """3 dense combined rounds at 2 parties x 1 van worker; returns
    {party: [per-round list of per-key outs]}. Multi-key rounds so the
    party server's batched WAN forward (the pull=True combined hop)
    carries the codec on every leg."""
    kw = dict(num_parties=2, workers_per_party=1,
              extra_cfg={"wire_codec": policy,
                         "wire_2bit_threshold": THR})
    if party_mesh_size:
        kw["party_mesh_size"] = party_mesh_size
    topo = InProcessHiPS(**kw).start()
    result = {}
    try:
        def master_init(kv):
            for k, n in zip(KEYS, SIZES):
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        def worker(kv):
            widx = 0 if kv is topo.workers[0] else 1
            outs = [np.zeros(n, np.float32) for n in SIZES]
            for k, o in zip(KEYS, outs):
                kv.init(k, o.copy())
                kv.pull(k, out=o)
            kv.wait()
            per_round = []
            for rnd in range(rounds):
                grads = [_g(widx, rnd, k, n)
                         for k, n in zip(KEYS, SIZES)]
                fut = kv.push_pull_async(KEYS, grads, outs)
                fut.wait(timeout=120)
                per_round.append([o.copy() for o in outs])
            result[widx] = per_round

        topo.run_workers(worker, include_master=master_init, timeout=300)
    finally:
        topo.stop()
    assert len(result) == 2
    return result


def _oracle_dense(policy, rounds=ROUNDS):
    """Replay the protocol in numpy: per party p (1 worker each)
    agg_p = decode(encode(g_p)); WAN forward wan_p = decode(encode(agg_p));
    global store S = sum_p wan_p; global response rsp = decode(encode(S))
    (ONE encode per round — both parties get identical bytes); party
    response out_p = decode(encode(rsp)). 2-bit legs each have their own
    persistent residual stream, keyed like the wire's
    ((key, off) / ("fwd", ...) / ("rsp", ...) state keys)."""
    zeros = lambda n: np.zeros(n, np.float32)
    r_push = {(p, k): zeros(n) for p in (0, 1)
              for k, n in zip(KEYS, SIZES)}
    r_fwd = {(p, k): zeros(n) for p in (0, 1)
             for k, n in zip(KEYS, SIZES)}
    r_grsp = {k: zeros(n) for k, n in zip(KEYS, SIZES)}
    r_prsp = {(p, k): zeros(n) for p in (0, 1)
              for k, n in zip(KEYS, SIZES)}
    out = {0: [], 1: []}
    for rnd in range(rounds):
        ko = {0: [], 1: []}
        for k, n in zip(KEYS, SIZES):
            if policy == "fp16":
                agg = [_f16(_g(p, rnd, k, n)) for p in (0, 1)]
                S = _f16(agg[0]) + _f16(agg[1])
                rsp = _f16(S)
                outs = [_f16(rsp), _f16(rsp)]
            else:
                agg = [_qd(_g(p, rnd, k, n), r_push[(p, k)])
                       for p in (0, 1)]
                wan = [_qd(agg[p], r_fwd[(p, k)]) for p in (0, 1)]
                S = wan[0] + wan[1]
                rsp = _qd(S, r_grsp[k])
                outs = [_qd(rsp, r_prsp[(p, k)]) for p in (0, 1)]
            ko[0].append(outs[0])
            ko[1].append(outs[1])
        out[0].append(ko[0])
        out[1].append(ko[1])
    return out


@pytest.mark.parametrize("mesh", [0, 2],
                         ids=["dist_sync", "dist_sync_mesh"])
@pytest.mark.parametrize("policy", ["fp16", "2bit"])
def test_dense_wire_matches_numpy_oracle(policy, mesh):
    got = _run_dense_wire(policy, party_mesh_size=mesh)
    want = _oracle_dense(policy)
    for p in (0, 1):
        for rnd in range(ROUNDS):
            for ki in range(len(KEYS)):
                np.testing.assert_array_equal(
                    got[p][rnd][ki], want[p][rnd][ki],
                    err_msg=f"party {p} round {rnd} key {KEYS[ki]} "
                            f"policy {policy}")
    # the rounds did real work (quantized gradients flowed end to end)
    assert any(np.abs(a).sum() > 0
               for rnd in want[0] for a in rnd)


# ---------------------------------------------------------------------------
# BSC combined rounds: the "bsc16" sparse wire vs its oracle
# ---------------------------------------------------------------------------

BSC_SIZES = [8, 5, 12, 6]
BSC_KEYS = list(range(len(BSC_SIZES)))


def _run_bsc_wire(party_mesh_size=0):
    kw = dict(num_parties=2, workers_per_party=1,
              extra_cfg={"wire_codec": "fp16"})
    if party_mesh_size:
        kw["party_mesh_size"] = party_mesh_size
    topo = InProcessHiPS(**kw).start()
    result = {}
    try:
        def master_init(kv):
            for k, n in zip(BSC_KEYS, BSC_SIZES):
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        def worker(kv):
            widx = 0 if kv is topo.workers[0] else 1
            for k, n in zip(BSC_KEYS, BSC_SIZES):
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()
            vals, idxs = _bsc_inputs(widx)
            fut = kv.push_pull_bsc_batch_async(BSC_KEYS, vals, idxs)
            agg = fut.results(timeout=120)
            dense = {}
            for k, n in zip(BSC_KEYS, BSC_SIZES):
                buf = np.zeros(n, np.float32)
                avals, aidx = agg[k]
                np.add.at(buf, aidx, avals)
                dense[k] = buf
            result[widx] = dense

        topo.run_workers(worker, include_master=master_init, timeout=300)
    finally:
        topo.stop()
    assert len(result) == 2
    return result


def _bsc_inputs(widx):
    rng = np.random.RandomState(5 + widx)
    vals = [rng.rand(3).astype(np.float32) + 1.0 for _ in BSC_KEYS]
    idxs = [np.sort(rng.choice(n, 3, replace=False)) for n in BSC_SIZES]
    return vals, idxs


def _oracle_bsc():
    """bsc16 narrows the sparse VALUES to fp16 on every leg; indices
    are exact. Per party: dense_p = scatter(f16(vals_p)); WAN forward
    is the dense fp16 downgrade (a party server has no sparse selection
    of its own); global S = sum_p f16(dense_p); both response legs are
    exact-nonzero f16 — dense result f16(S)."""
    out = {}
    for k, n in zip(BSC_KEYS, BSC_SIZES):
        S = np.zeros(n, np.float32)
        for p in (0, 1):
            vals, idxs = _bsc_inputs(p)
            dense = np.zeros(n, np.float32)
            np.add.at(dense, idxs[k], _f16(vals[k]))
            S += _f16(dense)
        out[k] = _f16(S)
    return out


@pytest.mark.parametrize("mesh", [0, 2],
                         ids=["dist_sync", "dist_sync_mesh"])
def test_bsc_wire_matches_numpy_oracle(mesh):
    got = _run_bsc_wire(party_mesh_size=mesh)
    want = _oracle_bsc()
    for k in BSC_KEYS:
        np.testing.assert_array_equal(got[0][k], want[k])
        np.testing.assert_array_equal(got[1][k], want[k])
    assert any(np.abs(v).sum() > 0 for v in want.values())


# ---------------------------------------------------------------------------
# MPQ chunk routing + per-codec WAN telemetry
# ---------------------------------------------------------------------------

def test_mpq_routes_per_chunk_and_telemetry_breaks_out_codecs():
    """Two keys straddling size_lower_bound, sliced one chunk each: the
    head chunk goes fp16, the bulk chunk 2-bit, and
    telemetry.wan_bytes_by_codec sees BOTH codec families on the WAN
    (forwards inherit each chunk's codec; responses echo it)."""
    sizes = [4, 16]
    keys = [0, 1]
    topo = InProcessHiPS(
        num_parties=2, workers_per_party=1,
        extra_cfg={"wire_codec": "mpq", "size_lower_bound": 8,
                   "wire_2bit_threshold": THR}).start()
    try:
        def master_init(kv):
            for k, n in zip(keys, sizes):
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        def init_worker(kv):
            for k, n in zip(keys, sizes):
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        topo.run_workers(init_worker, include_master=master_init,
                         timeout=120)
        telemetry.reset()
        telemetry.enable(True)

        def train(kv):
            widx = 0 if kv is topo.workers[0] else 1
            outs = [np.zeros(n, np.float32) for n in sizes]
            grads = [_g(widx, 0, k, n) for k, n in zip(keys, sizes)]
            # 16-byte budget: key 0 (4 floats) and key 1 (16 floats)
            # land in separate chunks -> separate codecs
            fut = kv.push_pull_async(keys, grads, outs, slice_bytes=16)
            fut.wait(timeout=120)

        topo.run_workers(train, timeout=120)
        snap = telemetry.snapshot()
    finally:
        telemetry.reset()
        topo.stop()
    by_codec = telemetry.wan_bytes_by_codec(snap)
    assert by_codec.get("fp16", 0) > 0, by_codec
    assert by_codec.get("2bit", 0) > 0, by_codec
    # the breakdown partitions wan_bytes exactly
    assert sum(by_codec.values()) == telemetry.wan_bytes(snap)


# ---------------------------------------------------------------------------
# the acceptance number: >= 4x WAN byte drop with the 2-bit wire
# ---------------------------------------------------------------------------

def _wan_bytes_for(policy, n=4096, rounds=2):
    topo = InProcessHiPS(
        num_parties=2, workers_per_party=1,
        extra_cfg={"wire_codec": policy,
                   "wire_2bit_threshold": THR}).start()
    try:
        def master_init(kv):
            kv.init(0, np.zeros(n, np.float32))
            kv.init(1, np.zeros(n, np.float32))
            kv.wait()

        def init_worker(kv):
            kv.init(0, np.zeros(n, np.float32))
            kv.init(1, np.zeros(n, np.float32))
            kv.wait()

        topo.run_workers(init_worker, include_master=master_init,
                         timeout=120)
        telemetry.reset()
        telemetry.enable(True)   # count the training rounds only

        def train(kv):
            widx = 0 if kv is topo.workers[0] else 1
            outs = [np.zeros(n, np.float32) for _ in (0, 1)]
            for rnd in range(rounds):
                grads = [_g(widx, rnd, k, n) for k in (0, 1)]
                fut = kv.push_pull_async([0, 1], grads, outs)
                fut.wait(timeout=120)

        topo.run_workers(train, timeout=240)
        wb = telemetry.wan_bytes()
    finally:
        telemetry.reset()
        topo.stop()
    assert wb > 0
    return wb


def test_wan_bytes_drop_at_least_4x_with_2bit_wire():
    """Aggregator mode quantizes BOTH WAN directions (2-bit forward,
    2-bit response): at 16 KiB keys the bytes/round must drop >= 4x vs
    the raw wire (the ISSUE's acceptance floor; the actual pack ratio
    is ~16x, headroom covers message framing)."""
    raw = _wan_bytes_for("")
    quant = _wan_bytes_for("2bit")
    if quant * 4 > raw:
        # the registry is process-global: a prior topology's teardown
        # can land a few late frames inside this measurement window
        # (seen as ~3 raw-size frames inflating the 2-bit figure).
        # One remeasure shakes the stragglers out; a real codec
        # regression fails both times.
        raw = _wan_bytes_for("")
        quant = _wan_bytes_for("2bit")
    assert quant * 4 <= raw, (raw, quant)


# ---------------------------------------------------------------------------
# loss parity: what the bit-exact oracles above cannot say — that a model
# trained over the narrowed wire ends where the float32 wire's does
# ---------------------------------------------------------------------------

PARITY_D = 256
PARITY_SPLIT = 64       # key 0 holds 64 elements, key 1 the other 192
# gap in final loss allowed against the float32 wire, from a first loss
# of about 1.0: fp16 only rounds; a 2-bit leg sends {0, +thr, -thr}, its
# error feedback closes the rest down to a noise ball
PARITY_TOL = {"fp16": 1e-4, "2bit": 0.05, "mpq": 0.05}


@functools.lru_cache(maxsize=None)
def _regression_loss(policy, rounds=200, n_samples=64, lr=0.05):
    """Mean final local loss of a 2-worker linear regression (a shard of
    data each) whose summed gradient crosses the combined wire every
    round, SGD applied worker-side: the servers only aggregate, so both
    workers read the same response bytes and stay in step. The weights
    are two keys either side of ``size_lower_bound``, so "mpq" sends
    one as fp16 and one as 2-bit."""
    keys = [0, 1]
    topo = InProcessHiPS(
        num_parties=2, workers_per_party=1,
        extra_cfg={"wire_codec": policy, "size_lower_bound": 128,
                   "wire_2bit_threshold": THR}).start()
    losses = {}

    def split(x):
        return [part.copy() for part in np.split(x, [PARITY_SPLIT])]

    try:
        def master_init(kv):
            for k, part in zip(keys, split(np.zeros(PARITY_D, np.float32))):
                kv.init(k, part)
            kv.wait()

        def worker(kv):
            widx = topo.workers.index(kv)
            w_true = (np.random.RandomState(7).randn(PARITY_D)
                      / np.sqrt(PARITY_D)).astype(np.float32)
            X = np.random.RandomState(42 + widx).randn(
                n_samples, PARITY_D).astype(np.float32)
            y = X @ w_true
            w = np.zeros(PARITY_D, np.float32)
            master_init(kv)
            outs = split(w)
            for _ in range(rounds):
                grad = (2.0 / n_samples) * (X.T @ (X @ w - y))
                # a budget of the larger key: a chunk a key, so a codec a key
                kv.push_pull_async(keys, split(grad), outs,
                                   slice_bytes=4 * (PARITY_D - PARITY_SPLIT)
                                   ).wait(timeout=60)
                w -= lr * np.concatenate(outs) / 2.0   # mean of 2 workers
            r = X @ w - y
            losses[widx] = float(np.mean(r * r))

        topo.run_workers(worker, include_master=master_init, timeout=120)
    finally:
        topo.stop()
    return (losses[0] + losses[1]) / 2.0


@pytest.mark.parametrize("policy", sorted(PARITY_TOL))
def test_narrowed_wire_reaches_the_float32_wires_loss(policy):
    raw = _regression_loss("")
    assert raw < 1e-3, f"the float32 wire itself did not converge: {raw}"
    got = _regression_loss(policy)
    assert got - raw <= PARITY_TOL[policy], (policy, got, raw)
