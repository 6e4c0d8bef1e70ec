"""A Bi-Sparse round's aggregate stays sparse on the servers.

Where every push of a (key, shard) round arrives on a ``bsc`` / ``bsc16``
wire and the server is an FSA aggregator, the global server merges index
lists (``compression.Entries``) and the party server hands the pull-back
on, instead of scattering each push into ``np.zeros(n)`` and finding the
support again with ``np.nonzero``. The first half holds that path to the
dense path it replaces, which is still in the tree: the same pushes sent
dense run today's ``+=`` and non-zero filter, and the two answers must
agree to the bit. The second half shows the bypass through a live
two-party topology: a round with a dense wire, an updater, HFA or
MixedSync never goes sparse and gives the parameters the numpy sum gives.
"""

import logging
import threading
import tracemalloc
import types

import numpy as np
import pytest

from geomx_tpu import telemetry
from geomx_tpu.compression import (BSCCompressor, Entries, _generic_decompress,
                                   two_bit_dequantize, two_bit_quantize)
from geomx_tpu.kvstore.base import DATA_INIT
from geomx_tpu.kvstore.replication import ReplicationManager
from geomx_tpu.kvstore.server import KVStoreDistServer
from geomx_tpu.optimizer import SGD
from geomx_tpu.ps.kv_app import KVPairs, ReqMeta
from geomx_tpu.simulate import InProcessHiPS

KEY = 5


# ---------------------------------------------------------------------------
# servers without sockets (as tests/test_server_protocol.py builds them)
# ---------------------------------------------------------------------------

class RecordingApp:
    def __init__(self):
        self.responses = []

    def response(self, req, kvs=None, body=""):
        self.responses.append((req, kvs))


def _req(sender, ts, compr, head=0, pull=True):
    return ReqMeta(sender=sender, timestamp=ts, customer_id=0, push=True,
                   pull=pull, simple_app=False, head=head, body="",
                   priority=0, version=0, iters=0, compr=compr, num_merge=1)


def _server(parties, is_global, fsa_slice_elems=0):
    s = KVStoreDistServer.__new__(KVStoreDistServer)
    s._lock = threading.RLock()
    s._states, s._key_total = {}, {}
    s._party_nsrv, s._party_nsrv_by_sender = 1, {}
    s._fsa_slice_elems = fsa_slice_elems
    s.is_global_server = is_global
    s._tier = "global" if is_global else "local"
    s.sync_global_mode = True
    s.updater = s.ts_global = s.ts_local = None
    s.use_hfa = False
    s.gc = BSCCompressor(0.01)
    s.cfg = types.SimpleNamespace(bigarray_bound=1 << 40, num_parties=0,
                                  enable_central_worker=False)
    s.po_local = None
    s.po_global = types.SimpleNamespace(
        my_rank=0, num_servers=1, num_live_workers=lambda: parties)
    return s


def _global_round(pushes, n, wire, sparse_wire, fsa_slice_elems=0,
                  rounds=1):
    """``rounds`` FSA rounds of one key of ``n`` elements on a global
    server: party p pushes ``pushes[p] = (values, positions)`` with a
    combined push+pull. ``sparse_wire`` sends them as the ``wire``
    payload they are; without it the same pushes go dense (scattered by
    ``_generic_decompress``, the parent's ``decompress_push``) and run the
    dense ``+=`` and the non-zero filter. Returns the server and the
    last round's response of every party."""
    s = _server(len(pushes), True, fsa_slice_elems)
    app = RecordingApp()
    init = KVPairs(keys=[KEY], vals=[np.zeros(n, np.float32)], offsets=[0],
                   totals=[n], lens=[n])
    acts = []
    s._handle_one_key(_req(9, 0, "", head=DATA_INIT, pull=False), init, app,
                      True, True, acts, 0, KEY, 0, n, False)
    for fn in acts:
        fn()
    for rnd in range(rounds):
        app.responses.clear()
        for p, (vals, idx) in enumerate(pushes):
            vdt = np.float16 if wire == "bsc16" else np.float32
            if sparse_wire:
                kvs = KVPairs(keys=[KEY], vals=[vals.astype(vdt)],
                              aux=[idx], offsets=[0], totals=[n], lens=[n],
                              compr=wire)
            else:
                dense = _generic_decompress(wire, vals.astype(vdt), idx, n)
                kvs = KVPairs(keys=[KEY], vals=[dense], offsets=[0],
                              totals=[n], lens=[n])
            acts = []
            s._handle_one_key(_req(9 + 2 * p, 10 * rnd + p + 1, wire), kvs,
                              app, True, True, acts, 0, KEY, 0, n, False)
            for fn in acts:
                fn()
    by_sender = {r.sender: kvs for r, kvs in app.responses}
    assert len(by_sender) == len(pushes), "a party was not answered"
    return s, [by_sender[9 + 2 * p] for p in range(len(pushes))]


def _bits(a):
    a = np.ascontiguousarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _same_response(got, want):
    assert got.compr == want.compr and got.keys == want.keys
    assert got.offsets == want.offsets and got.lens == want.lens
    assert got.totals == want.totals and len(got.vals) == len(want.vals)
    for i in range(len(want.vals)):
        assert got.aux[i].dtype == want.aux[i].dtype == np.int32
        np.testing.assert_array_equal(got.aux[i], want.aux[i])
        assert got.vals[i].dtype == want.vals[i].dtype
        np.testing.assert_array_equal(_bits(got.vals[i]),
                                      _bits(want.vals[i]))


def _selections(parties, n, seed):
    """``parties`` selections of max(int(n * 0.01), 1) distinct sorted
    positions each, drawn from a narrow range so that they overlap."""
    rng = np.random.default_rng(seed)
    k = max(int(n * 0.01), 1)
    out = []
    for _ in range(parties):
        idx = np.sort(rng.choice(min(n, 3 * k), k, replace=False))
        out.append((rng.normal(size=k).astype(np.float32),
                    idx.astype(np.int32)))
    return out


@pytest.mark.parametrize("wire", ["bsc", "bsc16"])
@pytest.mark.parametrize("n", [1, 768, 1_000_000])
@pytest.mark.parametrize("parties", [1, 2, 3])
def test_sparse_aggregate_equals_the_dense_path(parties, n, wire):
    pushes = _selections(parties, n, seed=n + parties)
    sp_srv, sparse = _global_round(pushes, n, wire, sparse_wire=True)
    dn_srv, dense = _global_round(pushes, n, wire, sparse_wire=False)
    st = sp_srv._states[(KEY, 0)]
    # a key of one element is no sparser as entries: it stays an array
    assert (st.entries is not None) == (n > 1)
    assert dn_srv._states[(KEY, 0)].entries is None
    for got, want in zip(sparse, dense):
        if parties < 3:
            _same_response(got, want)
            continue
        # three terms: the same positions, each value within
        # 2(n-1)u sum|x| of the dense path's (correct (b)'s bound)
        np.testing.assert_array_equal(got.aux[0], want.aux[0])
        dense_abs = np.zeros(n, np.float32)
        for vals, idx in pushes:
            np.add.at(dense_abs, idx, np.abs(vals))
        u = 2.0 ** -24 if wire == "bsc" else 2.0 ** -11
        bound = 2 * (parties - 1) * u * dense_abs[got.aux[0]] + 1e-30
        assert (np.abs(got.vals[0].astype(np.float64)
                       - want.vals[0].astype(np.float64)) <= bound).all()
    # the store read as an array is the dense path's store
    np.testing.assert_array_equal(st.stored, dn_srv._states[(KEY, 0)].stored)


def _two_party_case(case, n=768):
    a = (np.array([1.5, -2.0, 0.25, 4.0], np.float32),
         np.array([3, 40, 41, 700], np.int32))
    b = (np.array([2.5, 2.0, -8.0], np.float32),
         np.array([3, 40, 500], np.int32))
    if case == "explicit_zeros":
        # S12: a party's selection at a boundary of 0 carries zeros
        a = (np.array([0.0, -2.0, 0.0, 4.0], np.float32), a[1])
    elif case == "cancel_to_zero":
        b = (np.array([-1.5, 2.0, -8.0], np.float32), b[1])
    elif case == "duplicates_in_one_push":
        b = (np.array([2.5, 2.0, -8.0, 0.5, 7.0], np.float32),
             np.array([40, 3, 500, 40, 40], np.int32))
    elif case == "out_of_range":
        b = (np.array([2.5, 2.0, -8.0, 9.0, 9.0], np.float32),
             np.array([3, 40, 500, n, -1], np.int32))
    return [a, b]


@pytest.mark.parametrize("wire", ["bsc", "bsc16"])
@pytest.mark.parametrize("case", ["explicit_zeros", "cancel_to_zero",
                                  "duplicates_in_one_push", "out_of_range",
                                  "two_canonical_ranges"])
def test_sparse_aggregate_edge_cases(case, wire, caplog):
    n = 768
    pushes = _two_party_case(case, n)
    # two fine FSA states a key: one push entry is cut at element 384
    fine = n // 2 if case == "two_canonical_ranges" else 0
    with caplog.at_level(logging.WARNING, logger="geomx.compression"):
        sp_srv, sparse = _global_round(pushes, n, wire, True, fine)
        warned = sum("out-of-range" in r.getMessage()
                     for r in caplog.records)
        _dn_srv, dense = _global_round(pushes, n, wire, False, fine)
    assert warned == (1 if case == "out_of_range" else 0)
    assert len(sp_srv._states) == (2 if fine else 1)
    assert all(st.entries is not None for st in sp_srv._states.values())
    for got, want in zip(sparse, dense):
        _same_response(got, want)
    got = np.concatenate([sparse[0].aux[i] + sparse[0].offsets[i]
                          for i in range(len(sparse[0].aux))])
    if case == "explicit_zeros":
        # the zero pushed at 41 is gone; 0 + 2.5 at 3 stays
        assert 41 not in got and 3 in got
    if case == "cancel_to_zero":
        # 1.5 - 1.5 at 3 and -2 + 2 at 40 are gone
        np.testing.assert_array_equal(got, [41, 500, 700])
    if case == "duplicates_in_one_push":
        assert sparse[0].vals[0][list(got).index(40)] == \
            np.float32(-2.0 + 2.5 + 0.5 + 7.0)


@pytest.mark.parametrize("wire", ["bsc", "bsc16"])
def test_party_pull_back_in_slices_is_handed_on(wire):
    """A party server's shard answered by two global ranks (the P3 /
    MultiGPS shape: two parts of one state): the Bi-Sparse parts are
    joined by offset and the workers' ack is those entries; sent dense,
    the same parts run ``np.concatenate`` and the non-zero filter."""
    n, cut = 1000, 600
    parts = [(0, cut, np.array([1.0, -3.0, 0.5], np.float32),
              np.array([0, 17, 599], np.int32)),
             (cut, n, np.array([2.0, 4.0], np.float32),
              np.array([0, 399], np.int32))]

    def run(sparse_wire):
        s = _server(2, False)
        responses = {}
        s.worker_global = types.SimpleNamespace(
            take_failure=lambda ts: None,
            take_response=lambda ts: responses[ts])
        st = s._state(KEY, 0)
        st.stored = np.zeros(n, np.float32)
        st.length = st.total = n
        st.initialized = st.staging = True
        st.cycle, st.fwd_acks_left, st.fwd_expected = 7, 2, 2
        app = RecordingApp()
        st.deferred_acks = [(_req(11, 1, wire), app)]
        vdt = np.float16 if wire == "bsc16" else np.float32
        for rank, (lo, hi, vals, idx) in enumerate(parts):
            if sparse_wire:
                kvs = KVPairs(keys=[KEY], vals=[vals.astype(vdt)],
                              aux=[idx], offsets=[lo], totals=[n],
                              lens=[hi - lo], compr=wire)
            else:
                kvs = KVPairs(keys=[KEY], vals=[_generic_decompress(
                    wire, vals.astype(vdt), idx, hi - lo)], offsets=[lo],
                    totals=[n], lens=[hi - lo])
            responses[rank] = [kvs]
            s._on_global_push_ack_batch(
                [(KEY, 0, 7, lo, hi, n, None, None)], rank, rank)
        assert not st.staging and st.version == 1
        (_r, out), = app.responses
        return st, out

    st, got = run(True)
    dense_st, want = run(False)
    assert st.entries is not None and dense_st.entries is None
    _same_response(got, want)
    np.testing.assert_array_equal(got.aux[0], [0, 17, 599, 600, 999])
    np.testing.assert_array_equal(st.stored, dense_st.stored)


def test_steady_state_sparse_round_allocates_under_one_key():
    """A second round of a 4M-element key on the global server, two
    parties at 1%: index lists are merged, no array of the key's size is
    built (the dense path scatters each push into ``np.zeros(n)``, adds
    it into a third and filters the store once a puller: several n)."""
    n = 4_000_000
    pushes = _selections(2, n, seed=3)
    peaks = {}
    for sparse_wire in (True, False):
        tracemalloc.start()
        try:
            # the first round is inside the trace: its peak is reset below
            s, _ = _global_round(pushes, n, "bsc", sparse_wire)
            app = RecordingApp()
            kvs = [KVPairs(keys=[KEY], vals=[v], aux=[i], offsets=[0],
                           totals=[n], lens=[n], compr="bsc")
                   if sparse_wire else
                   KVPairs(keys=[KEY], vals=[_generic_decompress(
                       "bsc", v, i, n)], offsets=[0], totals=[n], lens=[n])
                   for v, i in pushes]
            tracemalloc.reset_peak()
            start = tracemalloc.get_traced_memory()[0]
            for p, kv in enumerate(kvs):
                acts = []
                s._handle_one_key(_req(9 + 2 * p, 20 + p, "bsc"), kv, app,
                                  True, True, acts, 0, KEY, 0, n, False)
                for fn in acts:
                    fn()
            peaks[sparse_wire] = tracemalloc.get_traced_memory()[1] - start
        finally:
            tracemalloc.stop()
        assert len(app.responses) == 2
    assert peaks[True] < n, peaks[True] / n
    assert peaks[False] > 4 * n, peaks[False] / n


def test_entries_from_wire_keeps_the_wire_arrays():
    """A payload in order (every selection, every server response) is
    taken as it is: no copy of positions or float32 values."""
    idx = np.array([2, 5, 9], np.int32)
    vals = np.array([1.0, 2.0, 3.0], np.float32)
    e = Entries.from_wire(vals, idx, 10)
    assert np.shares_memory(e.idx, idx) and np.shares_memory(e.vals, vals)
    assert e.size == 10
    assert e[0:10] is e
    np.testing.assert_array_equal(e[3:10].idx, [2, 6])
    np.testing.assert_array_equal(e.placed(5, 20).idx, [7, 10, 14])


# ---------------------------------------------------------------------------
# the bypass, through a live two-party topology
# ---------------------------------------------------------------------------

N = 64
THR = 0.5


def _grad(party, rnd):
    rng = np.random.RandomState(100 + 7 * party + rnd)
    return rng.uniform(-1, 1, N).astype(np.float32)


def _f16(x):
    return np.asarray(x, np.float32).astype(np.float16).astype(np.float32)


def _qd(x, residual):
    """One 2-bit wire leg: quantize with error feedback, dequantize."""
    return two_bit_dequantize(two_bit_quantize(
        np.asarray(x, np.float32), residual, THR), x.size, THR)


def _expected(mode, w0):
    """Worker parameters after each of the two rounds, replayed in numpy
    (one worker a party, so a party's aggregate is its worker's push)."""
    g = [[_grad(p, r) for p in (0, 1)] for r in (0, 1)]
    if mode == "dense":
        return [g[r][0] + g[r][1] for r in (0, 1)]
    if mode == "fp16":
        return [_f16(_f16(_f16(g[r][0]) + _f16(g[r][1]))) for r in (0, 1)]
    if mode == "2bit":
        zeros = lambda: np.zeros(N, np.float32)     # noqa: E731
        push, fwd = [zeros(), zeros()], [zeros(), zeros()]
        grsp, prsp = zeros(), [zeros(), zeros()]
        out = []
        for r in (0, 1):
            wan = [_qd(_qd(g[r][p], push[p]), fwd[p]) for p in (0, 1)]
            rsp = _qd(wan[0] + wan[1], grsp)
            outs = [_qd(rsp, prsp[p]) for p in (0, 1)]
            np.testing.assert_array_equal(outs[0], outs[1])
            out.append(outs[0])
        return out
    if mode == "updater":
        w1 = w0 - (g[0][0] + g[0][1])
        return [w1, w1 - (g[1][0] + g[1][1])]
    if mode == "hfa":
        # K2 = 2: round 1 stays in the party (its worker's push is the
        # store); round 2 ships (push - milestone) / parties, the
        # milestone being the init-time pull-back, and the pulled sum
        # lands on the milestone. Both workers push the same.
        x1, x2 = g[0][0], g[1][0]
        delta = (x2 - w0) / np.float32(2)
        return [x1, w0 + (delta + delta)]
    raise AssertionError(mode)


def _counters(prefix):
    return sum(v for k, v in telemetry.snapshot()["counters"].items()
               if k.startswith(prefix))


@pytest.mark.parametrize("mode", ["dense", "fp16", "2bit", "updater",
                                  "hfa", "mixed_sync"])
def test_rounds_that_never_go_sparse(mode):
    """Two rounds of one key: no (key, shard) round is stored as entries,
    every worker reads the numpy sum's parameters to the bit."""
    kw = dict(num_parties=2, workers_per_party=1)
    if mode in ("fp16", "2bit"):
        kw["extra_cfg"] = {"wire_codec": mode, "wire_2bit_threshold": THR}
    if mode == "hfa":
        kw.update(use_hfa=True, hfa_k2=2)
    topo = InProcessHiPS(**kw).start(sync_global=mode != "mixed_sync")
    w0 = np.linspace(-1, 1, N).astype(np.float32)
    got = {}
    try:
        if mode in ("updater", "mixed_sync"):
            topo.master.set_optimizer(SGD(learning_rate=1.0))

        def master_init(kv):
            kv.init(KEY, w0)
            kv.wait()

        def init(kv):
            kv.init(KEY, w0)
            np.testing.assert_array_equal(kv.pull(KEY), w0)

        topo.run_workers(init, include_master=master_init, timeout=60)
        telemetry.reset()
        telemetry.enable(True)

        def train(kv):
            p = topo.workers.index(kv)
            outs = []
            for rnd in (0, 1):
                grad = _grad(0 if mode == "hfa" else p, rnd)
                out = np.zeros(N, np.float32)
                if mode in ("fp16", "2bit"):
                    kv.push_pull_async(KEY, grad, out).wait(timeout=60)
                else:
                    kv.push(KEY, grad)
                    kv.pull(KEY, out=out)
                    kv.wait()
                outs.append(out)
            got[p] = outs

        topo.run_workers(train, timeout=120)
        sparse = _counters("server.sparse_key_rounds")
        dense = _counters("server.dense_key_rounds")
        final = topo.master.pull(KEY) if mode == "mixed_sync" else None
    finally:
        telemetry.reset()
        topo.stop()
    assert sparse == 0 and dense > 0
    if mode == "mixed_sync":
        # no barrier: a worker reads one or both parties' updates of a
        # round; with every ack back the global store holds all four
        grads = [_grad(p, r) for r in (0, 1) for p in (0, 1)]
        for p in (0, 1):
            assert np.abs(got[p][1] - w0).sum() > 0
        np.testing.assert_allclose(final, w0 - np.sum(grads, axis=0),
                                   rtol=0, atol=1e-5)
        return
    want = _expected(mode, w0)
    for p in (0, 1):
        for rnd in (0, 1):
            np.testing.assert_array_equal(
                _bits(got[p][rnd]), _bits(want[rnd]),
                err_msg=f"{mode}: worker {p} round {rnd}")


SIZES = [8, 768, 20_000]


def _bsc_topology(rounds, after=None):
    """Two parties x one worker, every key pushed and pulled on the
    Bi-Sparse wire for ``rounds`` rounds, Bi-Sparse on the party->global
    hop too; returns (every worker's dense aggregate of the last round
    per key, the expected one, counters).

    The party servers select at threshold 0.5: their sampled boundary is
    0 (a worker's push fills 1% of the key), so each ships the first
    half of the key in index order, explicit zeros included (PERF.md
    section 7), which holds every position ``_selections`` draws: the
    round's aggregate is the plain sum, every round."""
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    keys = list(range(len(SIZES)))
    sel = {(p, k): _selections(1, n, seed=31 * p + k)[0]
           for p in (0, 1) for k, n in zip(keys, SIZES)}
    got = {}
    try:
        def master_init(kv):
            kv.set_gradient_compression({"type": "bsc", "threshold": 0.5})
            for k, n in zip(keys, SIZES):
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        def init(kv):
            for k, n in zip(keys, SIZES):
                kv.init(k, np.zeros(n, np.float32))
                kv.pull(k, out=np.zeros(n, np.float32))
            kv.wait()

        topo.run_workers(init, include_master=master_init, timeout=60)
        telemetry.reset()
        telemetry.enable(True)

        def train(kv):
            p = topo.workers.index(kv)
            for _ in range(rounds):
                agg = kv.push_pull_bsc_batch(
                    keys, [sel[(p, k)][0] for k in keys],
                    [sel[(p, k)][1].astype(np.int64) for k in keys],
                    timeout=60)()
            dense = []
            for k, n in zip(keys, SIZES):
                d = np.zeros(n, np.float32)
                d[agg[k][1]] = agg[k][0]
                dense.append(d)
            got[p] = dense

        topo.run_workers(train, timeout=120)
        counters = {name: _counters("server." + name)
                    for name in ("sparse_key_rounds", "dense_key_rounds",
                                 "aggregate_ms")}
        extra = after(topo, keys) if after else None
    finally:
        telemetry.reset()
        topo.stop()
    want = []
    for k, n in zip(keys, SIZES):
        d = np.zeros(n, np.float32)
        for p in (0, 1):
            np.add.at(d, sel[(p, k)][1], sel[(p, k)][0])
        want.append(d)
    return got, want, counters, extra


def test_bi_sparse_round_counts_every_key_on_every_server():
    rounds = 2
    got, want, counters, _ = _bsc_topology(rounds)
    for p in (0, 1):
        for a, b in zip(got[p], want):
            np.testing.assert_array_equal(_bits(a), _bits(b))
    # each key: one round on the global server, one on each party server
    assert counters["sparse_key_rounds"] == len(SIZES) * 3 * rounds
    assert counters["dense_key_rounds"] == 0
    assert counters["aggregate_ms"] > 0


def test_dense_readers_of_a_sparse_store_get_the_aggregate():
    """After a Bi-Sparse round the store is entries; a dense pull, a
    replication snapshot and ``_snapshot_states`` read the dense
    aggregate through ``st.stored``."""
    def after(topo, keys):
        srv_states = []
        for srv in topo.servers:
            srv_states.append([srv._states[(k, 0)] for k in keys])
            assert all(st.entries is not None for st in srv_states[-1])
            assert all(st._dense is None for st in srv_states[-1])
            assert srv._snapshot_states() == {}       # no updater: no slots
        snaps = [ReplicationManager(srv, srv.cfg)._collect_dirty()
                 for srv in topo.servers]
        pulled = [topo.workers[0].pull(k) for k in keys]
        return snaps, pulled, srv_states

    _got, want, _c, (snaps, pulled, srv_states) = _bsc_topology(1, after)
    for k, d in enumerate(want):
        np.testing.assert_array_equal(pulled[k].ravel(), d)
        for snap in snaps:
            np.testing.assert_array_equal(snap[(k, 0)]["v"], d)
            assert snap[(k, 0)]["v"].dtype == np.float32
    # reading made the stores dense once; the entries are still the store
    for states in srv_states:
        for st, d in zip(states, want):
            assert st.entries is not None
            np.testing.assert_array_equal(st.stored, d)
