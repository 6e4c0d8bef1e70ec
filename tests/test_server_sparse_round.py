"""A Bi-Sparse round's aggregate stays sparse on the servers.

Where every push of a (key, shard) round arrives on a ``bsc`` / ``bsc16``
wire and the server is an FSA aggregator, the global server merges index
lists (``compression.Entries``) and the party server hands the pull-back
on, instead of scattering each push into ``np.zeros(n)`` and finding the
support again with ``np.nonzero``. The first half holds that path to the
dense path it replaces, which is still in the tree: the same pushes sent
dense run today's ``+=`` and non-zero filter, and the two answers must
agree to the bit. The party server's forward half is held the same way:
its workers' selections reach its own Bi-Sparse pass as ``Pairs``, and
what it sends on is what ``_generic_decompress`` and the dense pass give.
The second half shows the bypass through a live two-party topology: a
round with a dense wire, an updater, HFA or MixedSync never goes sparse
and gives the parameters the numpy sum gives.
"""

import logging
import sys
import threading
import tracemalloc
import types

import numpy as np
import pytest

from geomx_tpu import kernels_native, telemetry
from geomx_tpu.compression import (BSCCompressor, Entries, FP16Compressor,
                                   MPQCompressor, Pairs, _generic_decompress,
                                   make_compressor, two_bit_dequantize,
                                   two_bit_quantize)
from geomx_tpu.compression.entries import encode_positions
from geomx_tpu.kvstore.base import DATA_INIT
from geomx_tpu.kvstore import server as server_mod
from geomx_tpu.kvstore.replication import ReplicationManager
from geomx_tpu.kvstore.server import _SelectPool
from geomx_tpu.optimizer import SGD
from geomx_tpu.ps.kv_app import KVPairs
from geomx_tpu.simulate import InProcessHiPS
from tests.harness import (KEY, RecordingApp, SingleTier, _parallel,
                           link_positions, party_batch_push,
                           party_server_without_sockets as _party_server,
                           push_req as _req,
                           server_without_sockets as _server)


def _party_push(s, app, sender, ts, wire, vals, idx, n, num_merge=1):
    """One worker's combined push+pull of ``KEY`` on the local tier:
    ``wire`` "" sends the selection as the dense array it stands for."""
    if wire:
        vdt = np.float16 if wire == "bsc16" else np.float32
        kvs = KVPairs(keys=[KEY], vals=[vals.astype(vdt, copy=False)],
                      aux=[idx], offsets=[0], totals=[n], lens=[n],
                      compr=wire)
    else:
        kvs = KVPairs(keys=[KEY], vals=[_generic_decompress(
            "bsc", vals, idx, n)], offsets=[0], totals=[n], lens=[n])
    acts = []
    s._handle_one_key(_req(sender, ts, wire, num_merge=num_merge), kvs, app,
                      False, False, acts, 0, KEY, 0, n, False)
    for fn in acts:
        fn()


def _global_round(pushes, n, wire, sparse_wire, fsa_slice_elems=0,
                  rounds=1, link=False):
    """``rounds`` FSA rounds of one key of ``n`` elements on a global
    server: party p pushes ``pushes[p] = (values, positions)`` with a
    combined push+pull. ``sparse_wire`` sends them as the ``wire``
    payload they are; without it the same pushes go dense (scattered by
    ``_generic_decompress``, the parent's ``decompress_push``) and run the
    dense ``+=`` and the non-zero filter. ``link``: the requests come in
    on the global tier as a party server sends them, positions coded.
    Returns the server and the last round's response of every party."""
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
                              aux=[encode_positions(idx) if link else idx],
                              offsets=[0], totals=[n], lens=[n], compr=wire)
            else:
                dense = _generic_decompress(wire, vals.astype(vdt), idx, n)
                kvs = KVPairs(keys=[KEY], vals=[dense], offsets=[0],
                              totals=[n], lens=[n])
            acts = []
            s._handle_one_key(_req(9 + 2 * p, 10 * rnd + p + 1, wire,
                                   global_tier=link), kvs,
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
        # int32 positions, or their code where the payload was handed
        # to the party-global link: equal bytes either way
        assert got.aux[i].dtype == want.aux[i].dtype
        assert got.aux[i].dtype in (np.int32, np.uint8)
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
@pytest.mark.parametrize("fsa_slice_elems", [0, 300_000])
@pytest.mark.parametrize("n", [1, 768, 1_000_000])
def test_the_global_tier_is_answered_in_code_once_a_round(n, fsa_slice_elems,
                                                          wire):
    """Two parties' coded pushes on the global tier: the aggregate is
    the LAN form's to the bit, its positions come back coded (also
    where the store stayed an array and was filtered: a key of one
    element), a range's code is made once and every party is handed the
    same buffer, and each send of it is booked."""
    pushes = _selections(2, n, seed=n + 2)
    telemetry.reset()
    telemetry.enable(True)
    try:
        srv, coded = _global_round(pushes, n, wire, True, fsa_slice_elems,
                                   rounds=2, link=True)
        booked = (_counters("wire.index_bytes_coded"),
                  _counters("wire.index_bytes_plain"))
    finally:
        telemetry.reset()
    _srv, plain = _global_round(pushes, n, wire, True, fsa_slice_elems,
                                rounds=2)
    sent = 0
    for got, want in zip(coded, plain):
        assert got.keys == want.keys and got.offsets == want.offsets
        assert got.compr == want.compr == wire
        for i in range(len(want.vals)):
            np.testing.assert_array_equal(link_positions(got, i),
                                          want.aux[i])
            np.testing.assert_array_equal(_bits(got.vals[i]),
                                          _bits(want.vals[i]))
            sent += got.aux[i].nbytes
    a, b = coded
    assert len(a.aux) == len(b.aux) == (4 if fsa_slice_elems and n > 1e5
                                        else 1)
    # a store that is entries: the one buffer; an array (n = 1), filtered
    # again a puller, equal bytes
    assert all(x is y if n > 1 else x.tobytes() == y.tobytes()
               for x, y in zip(a.aux, b.aux))
    assert booked == (2 * sent, 0)


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


def _unsorted(sel):
    """A selection in the order ``lax.top_k`` hands it over: by
    magnitude, not by position."""
    vals, idx = sel
    by = np.argsort(-np.abs(vals), kind="stable")
    return vals[by], idx[by]


def _party_rounds(workers, wire, global_servers, n, rounds, sparse=True):
    """``rounds`` rounds of ``workers`` workers' overlapping selections
    on a party server; each forward is answered as a global server of
    this one party would answer it, with the forward itself. Returns
    per round what went to the global tier and the workers' acks, and
    how many (key, shard) rounds went forward sparse."""
    s = _party_server(workers, global_servers, n)
    if not sparse:
        s._forwards_sparse = lambda st, n: False    # the parent's path
    telemetry.reset()
    telemetry.enable(True)
    out = []
    try:
        for rnd in range(rounds):
            app = RecordingApp()
            pushes = [_unsorted(sel) for sel in
                      _selections(workers, n, seed=100 * rnd + workers)]
            for w, (vals, idx) in enumerate(pushes):
                _party_push(s, app, 9 + 2 * w, 10 * rnd + w, wire, vals,
                            idx, n)
            sent, s.worker_global.pushed = s.worker_global.pushed, []
            assert not app.responses, "acked before the pull-back"
            for ts, (kvs, _g, cb) in enumerate(sent):
                s.worker_global.responses[ts] = [kvs]
                cb(ts)
            out.append((pushes, sent, app.responses))
        forward = _counters("server.sparse_forward_key_rounds")
    finally:
        telemetry.reset()
    return out, forward


@pytest.mark.parametrize("wire", ["bsc", "bsc16"])
@pytest.mark.parametrize("global_servers", [1, 2])
@pytest.mark.parametrize("workers", [1, 2, 3])
def test_party_forward_equals_the_dense_recomputation(workers,
                                                      global_servers, wire):
    """Three rounds on a party server against numpy: the pushes
    scattered by ``_generic_decompress`` and summed in arrival order,
    each global slice through the dense ``compress_push`` of a
    compressor at the same point of the same generator. The same bytes
    go to the global tier, and the pull-back reaches every worker."""
    n, rounds = 20_000, 3
    got, forward = _party_rounds(workers, wire, global_servers, n, rounds)
    parent, none = _party_rounds(workers, wire, global_servers, n, rounds,
                                 sparse=False)
    assert forward == rounds and none == 0
    ref = BSCCompressor(0.01)
    vdt = np.float16 if wire == "bsc16" else np.float32
    cuts = [(g * n // global_servers, (g + 1) * n // global_servers)
            for g in range(global_servers)]
    for (pushes, sent, acks), (_p, parent_sent, _a) in zip(got, parent):
        dense = None
        for vals, idx in pushes:
            one = _generic_decompress(wire, vals.astype(vdt), idx, n)
            dense = one if dense is None else dense + one
        assert len(sent) == len(parent_sent) == global_servers
        want_idx, want_vals = [], []
        for (kvs, g_rank, _cb), (lo, hi), (pkvs, pg, _c) in zip(
                sent, cuts, parent_sent):
            vals, idx, _tag = ref.compress_push(dense[lo:hi], (KEY, lo))
            assert kvs.compr == wire and g_rank == pg
            assert kvs.offsets == [lo] and kvs.lens == [hi - lo]
            assert kvs.vals[0].dtype == vdt
            np.testing.assert_array_equal(link_positions(kvs), idx)
            np.testing.assert_array_equal(_bits(kvs.vals[0]),
                                          _bits(vals.astype(vdt)))
            _same_response(kvs, pkvs)
            want_idx.append(link_positions(kvs) + lo)
            want_vals.append(kvs.vals[0])
        # the pull-back (here: the forward itself) is every worker's ack
        assert len(acks) == workers
        for _r, ack in acks:
            assert ack.compr == wire
            np.testing.assert_array_equal(ack.aux[0],
                                          np.concatenate(want_idx))
            np.testing.assert_array_equal(_bits(ack.vals[0]),
                                          _bits(np.concatenate(want_vals)))


@pytest.mark.parametrize("num_merge", [1, 2])
def test_one_push_round_keeps_the_wire_arrays(num_merge):
    """One push a round (one worker, or a mesh party's merged selection
    counting for two): the staged aggregate is the wire's own arrays in
    the wire's order; nothing is sorted, nothing copied."""
    n = 20_000
    s = _party_server(num_merge, n=n)
    vals, idx = _unsorted(_selections(1, n, seed=4)[0])
    assert (np.diff(idx) < 0).any()
    _party_push(s, RecordingApp(), 9, 1, "bsc", vals, idx, n,
                num_merge=num_merge)
    st = s._states[(KEY, 0)]
    assert st.staging and type(st.outbound) is Pairs
    assert np.shares_memory(st.outbound.idx, idx)
    assert np.shares_memory(st.outbound.vals, vals)
    (kvs, _g, _cb), = s.worker_global.pushed
    assert kvs.compr == "bsc"


@pytest.mark.parametrize("first", ["bsc", "dense"])
def test_a_dense_push_makes_the_party_round_dense(first):
    """Two workers, one on the Bi-Sparse wire and one uncompressed: the
    round's aggregate is an array from the dense push on, and the
    forward is the dense pass's."""
    n = 20_000
    a, b = [_unsorted(sel) for sel in _selections(2, n, seed=8)]
    s = _party_server(2, n=n)
    telemetry.reset()
    telemetry.enable(True)
    try:
        app = RecordingApp()
        wires = ("bsc", "") if first == "bsc" else ("", "bsc")
        _party_push(s, app, 9, 1, wires[0], *a, n)
        st = s._states[(KEY, 0)]
        assert isinstance(st.merged, Pairs) == (first == "bsc")
        _party_push(s, app, 11, 2, wires[1], *b, n)
        forward = _counters("server.sparse_forward_key_rounds")
    finally:
        telemetry.reset()
    assert forward == 0 and isinstance(st.outbound, np.ndarray)
    dense = (_generic_decompress("bsc", *a, n)
             + _generic_decompress("bsc", *b, n))
    np.testing.assert_array_equal(st.outbound, dense)
    vals, idx, _t = BSCCompressor(0.01).compress_push(dense, (KEY, 0))
    (kvs, _g, _cb), = s.worker_global.pushed
    np.testing.assert_array_equal(link_positions(kvs), idx)
    np.testing.assert_array_equal(_bits(kvs.vals[0]), _bits(vals))


def test_round_released_early_forwards_the_pairs_it_holds():
    """A membership change completes a round with whatever ``st.merged``
    holds (``_on_membership`` -> ``_complete_local_round``): one of two
    workers' pushes, still ``Pairs``."""
    n = 20_000
    s = _party_server(2, n=n)
    vals, idx = _unsorted(_selections(1, n, seed=9)[0])
    app = RecordingApp()
    _party_push(s, app, 9, 1, "bsc", vals, idx, n)
    st = s._states[(KEY, 0)]
    assert not st.staging and not s.worker_global.pushed
    with st.lock:
        acts = s._complete_local_round(st, KEY)
    for fn in acts:
        fn()
    (kvs, _g, _cb), = s.worker_global.pushed
    want = BSCCompressor(0.01).compress_push(
        _generic_decompress("bsc", vals, idx, n), (KEY, 0))
    np.testing.assert_array_equal(link_positions(kvs), want[1])
    np.testing.assert_array_equal(_bits(kvs.vals[0]), _bits(want[0]))


def _make_dense(s, why):
    st = s._states[(KEY, 0)]
    if why == "single_tier":
        s.has_global_tier = False
    elif why == "hfa":
        s.use_hfa = True
    elif why == "tsengine_forward":
        s.ts_global = object()
    elif why == "no_compressor":
        s.gc = make_compressor(None)
    elif why == "fp16_compressor":
        s.gc = FP16Compressor()
    elif why == "mpq_small_key":
        s.gc = MPQCompressor(0.01, size_lower_bound=st.length + 1)
    elif why == "float16_key":
        st.stored = st.stored.astype(np.float16)
        st.dtype = np.dtype(np.float16)
    elif why == "before_init":
        st.stored = None
    else:
        raise AssertionError(why)


@pytest.mark.parametrize("why", ["single_tier", "hfa", "tsengine_forward",
                                 "no_compressor", "fp16_compressor",
                                 "mpq_small_key", "float16_key",
                                 "before_init"])
def test_party_server_takes_pairs_only_where_it_reselects(why):
    """Each condition alone turns the sparse forward off: the push is
    decompressed on arrival, as at the parent."""
    n = 768
    s = _party_server(2, n=n)
    st = s._states[(KEY, 0)]
    assert s._forwards_sparse(st, n)
    _make_dense(s, why)
    assert not s._forwards_sparse(st, n)
    if why == "before_init":
        return
    vals, idx = _unsorted(_selections(1, n, seed=2)[0])
    _party_push(s, RecordingApp(), 9, 1, "bsc", vals, idx, n)
    assert isinstance(st.merged, np.ndarray)
    np.testing.assert_array_equal(
        st.merged, _generic_decompress("bsc", vals, idx, n))


def test_steady_state_party_round_allocates_under_one_key():
    """A second round of a 1,000,000-element key on a party server, one
    worker at 1%: beside the compressor's standing ``u`` and ``v``
    nothing of the key's size is built (the parent scattered the push
    into ``np.zeros(n)``: over one key's bytes)."""
    n = 1_000_000
    peaks = {}
    for sparse in (True, False):
        s = _party_server(1, n=n)
        if not sparse:
            s._forwards_sparse = lambda st, n: False
        pushes = [_unsorted(_selections(1, n, seed=r)[0]) for r in (0, 1)]
        tracemalloc.start()
        try:
            app = RecordingApp()
            _party_push(s, app, 9, 1, "bsc", *pushes[0], n)
            (kvs, _g, cb), = s.worker_global.pushed
            s.worker_global.pushed = []
            s.worker_global.responses[0] = [kvs]
            cb(0)
            assert len(app.responses) == 1
            tracemalloc.reset_peak()
            start = tracemalloc.get_traced_memory()[0]
            _party_push(s, app, 9, 2, "bsc", *pushes[1], n)
            peaks[sparse] = tracemalloc.get_traced_memory()[1] - start
        finally:
            tracemalloc.stop()
        assert len(s.worker_global.pushed) == 1
    assert peaks[True] < 4 * n, peaks[True] / n
    assert peaks[False] > 4 * n, peaks[False] / n


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


def _sel(worker, rnd):
    """A worker's selection of 4 of ``N``: (values, positions in no
    order), all in the key's first half."""
    rng = np.random.RandomState(500 + 7 * worker + rnd)
    return (rng.uniform(-1, 1, 4).astype(np.float32),
            rng.choice(N // 2, 4, replace=False).astype(np.int64))


def _scattered(sel):
    out = np.zeros(N, np.float32)
    out[sel[1]] = sel[0]
    return out


def _expected(mode, w0):
    """Worker parameters after each of the two rounds, replayed in numpy
    (one worker a party, so a party's aggregate is its worker's push).
    In the ``bsc_*`` modes a worker pushes ``_sel`` on the Bi-Sparse
    wire and reads the round's aggregate."""
    if mode.startswith("bsc_"):
        x = [[_scattered(_sel(w, r)) for w in range(4)] for r in (0, 1)]
        if mode == "bsc_hfa":
            # as "hfa" below, both workers pushing worker 0's selection
            delta = (x[1][0] - w0) / np.float32(2)
            return [x[0][0], w0 + (delta + delta)]
        if mode == "bsc_mpq_small_key":
            return [_f16(_f16(x[r][0]) + _f16(x[r][1])) for r in (0, 1)]
        if mode == "bsc_joined_by_a_dense_push":
            # two workers a party; a party's selection at threshold 0.5
            # is the key's first half (see _bsc_topology), the whole sum
            return [(x[r][0] + x[r][1]) + (x[r][2] + x[r][3])
                    for r in (0, 1)]
        return [x[r][0] + x[r][1] for r in (0, 1)]
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


@pytest.mark.parametrize("mode", [
    "dense", "fp16", "2bit", "updater", "hfa", "mixed_sync", "bsc_hfa",
    "bsc_single_tier", "bsc_inter_ts", "bsc_mpq_small_key",
    "bsc_joined_by_a_dense_push"])
def test_rounds_that_never_go_sparse(mode):
    """Two rounds of one key: no (key, shard) round is stored as entries,
    none goes forward from a party server as pairs, every worker reads
    the numpy sum's parameters to the bit. The ``bsc_*`` modes push on
    the Bi-Sparse wire to a party server that cannot keep it sparse."""
    kw = dict(num_parties=2, workers_per_party=1)
    if mode in ("fp16", "2bit"):
        kw["extra_cfg"] = {"wire_codec": mode, "wire_2bit_threshold": THR}
    if mode in ("hfa", "bsc_hfa"):
        kw.update(use_hfa=True, hfa_k2=2)
    if mode == "bsc_inter_ts":
        kw["extra_cfg"] = {"enable_inter_ts": True}
    if mode == "bsc_joined_by_a_dense_push":
        kw["workers_per_party"] = 2
    if mode == "bsc_single_tier":
        topo = SingleTier(num_workers=2).start()

        def run_workers(fn, include_master=None, timeout=None):
            _parallel([lambda kv=kv: fn(kv) for kv in topo.workers],
                      timeout)
    else:
        topo = InProcessHiPS(**kw).start(sync_global=mode != "mixed_sync")
        run_workers = topo.run_workers
    w0 = np.linspace(-1, 1, N).astype(np.float32)
    got = {}
    try:
        if mode in ("updater", "mixed_sync"):
            topo.master.set_optimizer(SGD(learning_rate=1.0))

        def master_init(kv):
            if mode == "bsc_mpq_small_key":
                kv.set_gradient_compression(
                    {"type": "mpq", "size_lower_bound": N + 1})
            if mode == "bsc_joined_by_a_dense_push":
                kv.set_gradient_compression(
                    {"type": "bsc", "threshold": 0.5})
            kv.init(KEY, w0)
            kv.wait()

        def init(kv):
            kv.init(KEY, w0)
            np.testing.assert_array_equal(kv.pull(KEY), w0)

        run_workers(init, include_master=master_init, timeout=60)
        telemetry.reset()
        telemetry.enable(True)

        def train(kv):
            p = topo.workers.index(kv)
            outs = []
            for rnd in (0, 1):
                out = np.zeros(N, np.float32)
                if mode.startswith("bsc_") and not (
                        mode == "bsc_joined_by_a_dense_push" and p % 2):
                    vals, idx = _sel(0 if mode == "bsc_hfa" else p, rnd)
                    vals, idx = kv.push_pull_bsc_batch(
                        [KEY], [vals], [idx], timeout=60)()[KEY]
                    out[idx] = vals
                    outs.append(out)
                    continue
                grad = (_scattered(_sel(p, rnd)) if mode.startswith("bsc_")
                        else _grad(0 if mode == "hfa" else p, rnd))
                if mode in ("fp16", "2bit"):
                    kv.push_pull_async(KEY, grad, out).wait(timeout=60)
                else:
                    kv.push(KEY, grad)
                    kv.pull(KEY, out=out)
                    kv.wait()
                outs.append(out)
            got[p] = outs

        run_workers(train, timeout=120)
        sparse = _counters("server.sparse_key_rounds")
        dense = _counters("server.dense_key_rounds")
        forward = _counters("server.sparse_forward_key_rounds")
        final = topo.master.pull(KEY) if mode == "mixed_sync" else None
    finally:
        telemetry.reset()
        topo.stop()
    assert forward == 0
    if mode == "bsc_joined_by_a_dense_push":
        # the party's aggregate was an array; its re-selection still
        # leaves on the Bi-Sparse wire, and from there the round is sparse
        assert sparse > 0
    else:
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
    for p in sorted(got):
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
                                 "sparse_forward_key_rounds",
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
    # and each party server's forward ran from its worker's pairs
    assert counters["sparse_forward_key_rounds"] == len(SIZES) * 2 * rounds
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


# ---------------------------------------------------------------------------
# one round's re-selection over a pool of threads: bit for bit the serial
# result (a worker's combined push completes the round of all its keys in
# one _handle_data call; _flush_forward_batch draws every key's boundary
# sample first, in the batch's order, then compresses the large keys side
# by side)
# ---------------------------------------------------------------------------

MIXED = {3: 5_000, 4: 40, 7: 120_000, 9: 70_000, 11: 1, 12: 300,
         13: 70_000, 14: 33_000, 15: 2_048}
ABOVE_EVERY_KEY = 1 << 30


def _seeded_pushes(sizes, seed):
    """Every key's selection of one round: 1% of its positions in the
    order ``lax.top_k`` would give them."""
    rng = np.random.default_rng(seed)
    out = {}
    for key, n in sizes.items():
        idx = rng.choice(n, max(n // 100, 1), replace=False)
        out[key] = (rng.standard_normal(idx.size).astype(np.float32),
                    idx.astype(np.int32))
    return out


def _answer_forwards(s):
    """Complete the round as a global server of this one party would:
    every forwarded message answered with itself. Returns the messages."""
    sent, s.worker_global.pushed = s.worker_global.pushed, []
    for ts, (kvs, _g, cb) in enumerate(sent):
        s.worker_global.responses[ts] = [kvs]
        cb(ts)
    return sent


def _batched_rounds(monkeypatch, helpers, min_elems, global_servers=1,
                    sizes=MIXED, rounds=5):
    """``rounds`` rounds of one worker's combined pushes of ``sizes`` on
    a party server that selects over ``helpers`` threads beside its own
    (0: no pool, the serial path) -> (server, messages a round)."""
    monkeypatch.setattr(server_mod, "_POOL_MIN_ELEMS", min_elems)
    s = _party_server(1, global_servers, keys=sizes)
    if helpers:
        s._select_pool = _SelectPool(helpers)
    sent = []
    try:
        for rnd in range(rounds):
            party_batch_push(s, RecordingApp(), 9, rnd,
                             _seeded_pushes(sizes, 1000 + rnd),
                             trace_round=rnd)
            sent.append(_answer_forwards(s))
    finally:
        s._close_select_pool()
    return s, sent


def _same_forwards(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert [g for _k, g, _c in a] == [g for _k, g, _c in b]
        for (kvs, _g, _cb), (ref, _rg, _rcb) in zip(a, b):
            _same_response(kvs, ref)


def _same_compressor_state(got, want):
    assert list(got._u) == list(want._u) and list(got._v) == list(want._v)
    for k in want._u:
        np.testing.assert_array_equal(_bits(got._u[k]), _bits(want._u[k]))
        np.testing.assert_array_equal(_bits(got._v[k]), _bits(want._v[k]))
    assert got._rng.bit_generator.state == want._rng.bit_generator.state


@pytest.mark.parametrize("global_servers", [1, 2])
@pytest.mark.parametrize("min_elems", [0, 10_000, ABOVE_EVERY_KEY])
@pytest.mark.parametrize("helpers", [1, 2, 4])
def test_pooled_selection_is_the_serial_one_bit_for_bit(
        monkeypatch, helpers, min_elems, global_servers):
    """Five rounds on keys of 1 to 120,000 elements: the messages to the
    global tier (keys in the entries' order, positions, values), every
    key's ``u`` and ``v`` and the boundary sample's generator are what
    the pass without a pool leaves, whatever the pool's size and
    whichever keys it is handed."""
    serial, want = _batched_rounds(monkeypatch, 0, min_elems, global_servers)
    pooled, got = _batched_rounds(monkeypatch, helpers, min_elems,
                                  global_servers)
    slices = sum(min(global_servers, n) for n in MIXED.values())
    assert len(serial.gc._u) == slices
    assert all(sum(len(kvs.keys) for kvs, _g, _c in rnd) == slices
               for rnd in want)
    _same_forwards(got, want)
    _same_compressor_state(pooled.gc, serial.gc)


def test_pooled_selection_under_a_short_switch_interval(monkeypatch):
    """More threads than cores and the interpreter switching every 10
    us: still the serial result, and every (key, round) counted once."""
    sizes = {k: 3_000 + 517 * k for k in range(40)}
    serial, want = _batched_rounds(monkeypatch, 0, 0, sizes=sizes, rounds=8)
    telemetry.reset()
    telemetry.enable(True)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pooled, got = _batched_rounds(monkeypatch, 24, 0, sizes=sizes,
                                      rounds=8)
        forward = _counters("server.sparse_forward_key_rounds")
    finally:
        sys.setswitchinterval(was)
        telemetry.reset()
    assert forward == len(sizes) * 8
    _same_forwards(got, want)
    _same_compressor_state(pooled.gc, serial.gc)


@pytest.mark.parametrize("bad", [7, 12], ids=["pool_task", "calling_thread"])
def test_a_selection_that_raises_surfaces_and_frees_its_key(monkeypatch, bad):
    """A key whose compressor raises, on a thread of the pool (key 7,
    the largest) or on the calling thread (key 12, a small one): the
    error comes out of the forward, no key's lock stays held, and the
    pool takes the next batch."""
    monkeypatch.setattr(server_mod, "_POOL_MIN_ELEMS", 10_000)
    s = _party_server(1, keys=MIXED)
    s._select_pool = _SelectPool(2)
    real = s.gc.compress_push

    def compress_push(arr, state_key=None, **drawn):
        if state_key[0] == bad:
            raise RuntimeError(f"no selection for key {bad}")
        return real(arr, state_key, **drawn)

    try:
        s.gc.compress_push = compress_push
        with pytest.raises(RuntimeError, match=f"for key {bad}"):
            party_batch_push(s, RecordingApp(), 9, 0,
                             _seeded_pushes(MIXED, 1))
        assert s.worker_global.pushed == []
        states = {key: s._state(key, 0) for key in MIXED}

        def free(st):       # from another thread: the lock is re-entrant
            got = st.lock.acquire(blocking=False)
            if got:
                st.lock.release()
            return got

        assert all(_parallel([lambda st=st: free(st)
                              for st in states.values()]))
        # the round is still staged: forward it again, through the pool
        s.gc.compress_push = real
        s._flush_forward_batch([(key, 0, st.cycle)
                                for key, st in states.items()])
        (kvs, _g, _cb), = s.worker_global.pushed
        assert kvs.keys == list(MIXED)
        assert any(t.name.startswith("select") for t in
                   threading.enumerate())
    finally:
        s._close_select_pool()


@pytest.mark.parametrize("how", ["shutdown", "crash"])
def test_no_pool_thread_outlives_the_server(monkeypatch, how):
    monkeypatch.setattr(server_mod, "_POOL_MIN_ELEMS", 0)
    s = _party_server(1, keys=MIXED)
    s._select_pool = _SelectPool(4)
    s._crashed, s._stop = False, threading.Event()
    s.replication = types.SimpleNamespace(stop=lambda flush: None)
    for po in (s.po_local, s.po_global):
        po.finalize = lambda do_barrier: None
        po.van.stop = lambda: None
    before = set(threading.enumerate())
    party_batch_push(s, RecordingApp(), 9, 0, _seeded_pushes(MIXED, 1))
    want = _answer_forwards(s)
    mine = set(threading.enumerate()) - before
    assert mine and all(t.name.startswith("select") for t in mine)
    getattr(s, how)()
    assert not any(t.is_alive() for t in mine)
    # a push that still arrives is selected on its own thread
    party_batch_push(s, RecordingApp(), 9, 1, _seeded_pushes(MIXED, 2))
    assert len(_answer_forwards(s)) == len(want) == 1
    assert set(threading.enumerate()) <= before


def _forwarded_by_a_topology(monkeypatch, pooled, rounds=3):
    """Two parties x one worker, ``rounds`` Bi-Sparse rounds on keys of
    mixed sizes -> what each party server sent to the global tier, as
    bytes, in its order of sending."""
    monkeypatch.setattr(server_mod, "_POOL_MIN_ELEMS", 2_000)
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    keys = list(MIXED)
    sent = {}
    try:
        party_servers = [s for s in topo.servers if s.has_global_tier]
        assert len(party_servers) == 2
        for p, srv in enumerate(party_servers):
            assert isinstance(srv._select_pool, _SelectPool)
            if not pooled:
                srv._close_select_pool()
                srv._select_pool = None
            log = sent[p] = []

            def push(kvs, g_rank, _real=srv.worker_global.push, _log=log,
                     **kw):
                _log.append((g_rank, kvs.compr, list(kvs.keys),
                             list(kvs.offsets), list(kvs.lens),
                             [np.asarray(v).tobytes() for v in kvs.vals],
                             [np.asarray(a).tobytes() for a in kvs.aux]))
                return _real(kvs, g_rank, **kw)

            srv.worker_global.push = push

        def master_init(kv):
            kv.set_gradient_compression({"type": "bsc", "threshold": 0.05})
            for k, n in MIXED.items():
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        def init(kv):
            for k, n in MIXED.items():
                kv.init(k, np.zeros(n, np.float32))
                kv.pull(k, out=np.zeros(n, np.float32))
            kv.wait()

        topo.run_workers(init, include_master=master_init, timeout=60)

        def train(kv):
            p = topo.workers.index(kv)
            for rnd in range(rounds):
                sel = _seeded_pushes(MIXED, 77 * p + rnd)
                kv.push_pull_bsc_batch(
                    keys, [sel[k][0] for k in keys],
                    [sel[k][1].astype(np.int64) for k in keys],
                    timeout=60)()

        topo.run_workers(train, timeout=120)
    finally:
        topo.stop()
    assert not any(t.name.startswith("select") and t.is_alive()
                   for t in threading.enumerate())
    return sent


@pytest.mark.parametrize("second", ["pooled", "serial"])
def test_two_topologies_forward_the_same_bytes(monkeypatch, second):
    """A live two-party topology run twice: each party server's messages
    to the global tier are byte-equal, pool against pool and pool
    against none, and the pool's threads are gone with the servers."""
    first = _forwarded_by_a_topology(monkeypatch, pooled=True)
    again = _forwarded_by_a_topology(monkeypatch, pooled=second == "pooled")
    assert first == again
    for p in (0, 1):
        assert len(first[p]) == 3
        assert all(msg[2] == list(MIXED) for msg in first[p])


# ---------------------------------------------------------------------------
# the global server's merge of the parties' index lists: the native pass
# (native/kernels.cc gxk_entries_merge) against the numpy chain, through
# a live round
# ---------------------------------------------------------------------------

MERGED = {3: 5_000, 4: 300, 7: 120_000, 9: 70_000, 14: 33_000, 15: 2_048}


def _merged_by_a_topology(monkeypatch, native, rounds=2):
    """Two parties x one worker, ``rounds`` Bi-Sparse rounds on keys of
    mixed sizes, with the kernels' library or without -> (what crossed
    the party-global link in each direction as bytes, the global
    server's stored entries, every worker's last aggregate, counters)."""
    if not native:
        monkeypatch.setattr(kernels_native, "lib", lambda: None)
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    keys = list(MERGED)
    forwards, answers, got = {}, [], {}
    try:
        (gsrv,) = [s for s in topo.servers if s.is_global_server]
        party_servers = [s for s in topo.servers if s.has_global_tier]
        for p, srv in enumerate(party_servers):
            log = forwards[p] = []

            def push(kvs, g_rank, _real=srv.worker_global.push, _log=log,
                     **kw):
                _log.append((list(kvs.keys),
                             [np.asarray(v).tobytes() for v in kvs.vals],
                             [np.asarray(a).tobytes() for a in kvs.aux],
                             [(link_positions(kvs, i)[[0, -1]].tolist()
                               if np.asarray(kvs.vals[i]).size else None)
                              for i in range(len(kvs.keys))]))
                return _real(kvs, g_rank, **kw)

            srv.worker_global.push = push

        def response(req, kvs=None, body="",
                     _real=gsrv.server_global.response):
            # a round's answers: the bootstrap's pulls ask a key at a time
            if kvs is not None and list(kvs.keys) == keys:
                answers.append((req.sender, list(kvs.keys),
                                [np.asarray(v).tobytes() for v in kvs.vals],
                                [np.asarray(a).tobytes() for a in kvs.aux]))
            return _real(req, kvs, body)

        gsrv.server_global.response = response

        def master_init(kv):
            kv.set_gradient_compression({"type": "bsc", "threshold": 0.05})
            for k, n in MERGED.items():
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        def init(kv):
            for k, n in MERGED.items():
                kv.init(k, np.zeros(n, np.float32))
                kv.pull(k, out=np.zeros(n, np.float32))
            kv.wait()

        topo.run_workers(init, include_master=master_init, timeout=60)
        telemetry.reset()
        telemetry.enable(True)

        def train(kv):
            p = topo.workers.index(kv)
            for rnd in range(rounds):
                sel = _seeded_pushes(MERGED, 91 * p + rnd)
                agg = kv.push_pull_bsc_batch(
                    keys, [sel[k][0] for k in keys],
                    [sel[k][1].astype(np.int64) for k in keys],
                    timeout=60)()
            got[p] = {k: (np.asarray(agg[k][0]).tobytes(),
                          np.asarray(agg[k][1]).tolist()) for k in keys}

        topo.run_workers(train, timeout=120)
        counters = {name: _counters("server." + name) for name in (
            "native_merge_key_rounds", "numpy_merge_key_rounds",
            "sparse_key_rounds", "dense_key_rounds")}
        stored = {k: (gsrv._states[(k, 0)].entries.idx.tolist(),
                      gsrv._states[(k, 0)].entries.vals.tobytes())
                  for k in keys}
    finally:
        telemetry.reset()
        topo.stop()
    return forwards, sorted(answers), stored, got, counters


def test_a_round_merged_natively_is_the_round_merged_in_numpy(monkeypatch):
    """The same two-party round with the library and without: the bytes
    on the party-global link in both directions, the global server's
    store and what every worker applies are equal, and the counters say
    which pass made each (key, shard) merge."""
    if kernels_native.lib() is None:
        pytest.skip("no native kernels on this machine")
    rounds = 2
    with monkeypatch.context() as m:
        first = _merged_by_a_topology(m, native=True, rounds=rounds)
    second = _merged_by_a_topology(monkeypatch, native=False, rounds=rounds)
    forwards, answers, stored, got, counters = first
    assert (forwards, answers, stored, got) == second[:4]
    # every key's two forwards overlap, so whichever came first the
    # global server had a merge to make, once a key and round
    for rnd in range(rounds):
        for i, k in enumerate(MERGED):
            (a0, a1), (b0, b1) = (forwards[p][rnd][3][i] for p in (0, 1))
            assert max(a0, b0) <= min(a1, b1), (rnd, k)
    keys = len(MERGED)
    assert counters == {"native_merge_key_rounds": keys * rounds,
                        "numpy_merge_key_rounds": 0,
                        "sparse_key_rounds": keys * 3 * rounds,
                        "dense_key_rounds": 0}
    assert second[4] == {"native_merge_key_rounds": 0,
                         "numpy_merge_key_rounds": keys * rounds,
                         "sparse_key_rounds": keys * 3 * rounds,
                         "dense_key_rounds": 0}
    # a round's answer to each party carries every key's merged list
    assert len(answers) == 2 * rounds and all(any(a[2]) for a in answers)
    assert got[0] == got[1]
    # the store is the merged list without its exact zeros (a small
    # key's boundary of 0 ships zeros, PERF.md section 7)
    assert all(np.frombuffer(vals, np.float32).all()
               for _idx, vals in stored.values())
    assert len(stored[7][0]) > 100
