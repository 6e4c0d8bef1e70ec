"""Element-sparse wire (KVStoreDist.push_pull_bsc_batch, the blocking
one-chunk form of the sparse round, push_pull_bsc_batch_async).

The TPU-native BSC LAN hop (round-3 verdict item 3): a worker ships its
on-chip top-k selection as (values, indices) — O(k) bytes — the servers
aggregate, and the combined ack returns the aggregated gradient's exact
nonzero set. Semantics must equal a dense push_pull of the scattered
selection.
"""

import threading

import numpy as np
import pytest

from geomx_tpu.simulate import InProcessHiPS


def _run_workers(topo, worker_fn, master_init, timeout=300):
    # run_workers joins with a timeout, surfaces worker errors, and
    # raises on hang — no wrapper thread needed
    topo.run_workers(worker_fn, include_master=master_init,
                     timeout=timeout)


@pytest.mark.parametrize("sharded", [False, True])
def test_sparse_round_aggregates_and_ack_is_exact(sharded):
    """Two workers push overlapping sparse selections of one key; the
    aggregate the round returns must equal the dense sum exactly —
    overlapping indices sum, disjoint ones pass through."""
    n = 40
    # sharded=True: two local servers + a bigarray bound below the key
    # size forces the selection to be partitioned across server shards
    kw = dict(num_parties=2, workers_per_party=1)
    if sharded:
        kw.update(servers_per_party=2, bigarray_bound=16)
    topo = InProcessHiPS(**kw).start()
    results = {}
    try:
        def master_init(kv):
            kv.init(7, np.zeros(n, np.float32))
            kv.wait()

        def worker(kv):
            widx = 0 if kv is topo.workers[0] else 1
            kv.init(7, np.zeros(n, np.float32))
            kv.pull(7, out=np.zeros(n, np.float32))
            kv.wait()
            if widx == 0:
                idx = np.array([0, 5, 17, 33], np.int64)
                vals = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
            else:
                idx = np.array([5, 20, 39], np.int64)
                vals = np.array([10.0, 20.0, 30.0], np.float32)
            avals, aidx = kv.push_pull_bsc_batch([7], [vals], [idx])()[7]
            dense = np.zeros(n, np.float32)
            dense[aidx] = avals
            results[widx] = dense

        _run_workers(topo, worker, master_init)
    finally:
        topo.stop()

    expect = np.zeros(n, np.float32)
    expect[[0, 5, 17, 33]] += [1.0, 2.0, 3.0, 4.0]
    expect[[5, 20, 39]] += [10.0, 20.0, 30.0]
    np.testing.assert_allclose(results[0], expect)
    np.testing.assert_array_equal(results[0], results[1])


def test_sparse_round_range_check():
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    try:
        def master_init(kv):
            kv.init(3, np.zeros(8, np.float32))
            kv.wait()

        def worker(kv):
            kv.init(3, np.zeros(8, np.float32))
            kv.wait()
            with pytest.raises(IndexError):
                kv.push_pull_bsc_batch([3], [np.ones(1, np.float32)],
                                       [np.array([8], np.int64)])
            # the refused call sent nothing and must not poison the
            # round: a clean round still completes
            avals, aidx = kv.push_pull_bsc_batch(
                [3], [np.ones(1, np.float32)],
                [np.array([2], np.int64)])()[3]
            dense = np.zeros(8, np.float32)
            dense[aidx] = avals
            np.testing.assert_allclose(dense[2], 2.0)

        _run_workers(topo, worker, master_init)
    finally:
        topo.stop()


def test_trainer_indices_beyond_2p24():
    """Round-3 verdict item 3: the float32-mantissa index packing capped
    the trainer at 2^24 params. Indices now travel as bitcast int32 —
    verify exactness of a selection ABOVE 2^24 on a 17M-element leaf."""
    import jax.numpy as jnp

    from geomx_tpu.kvstore import create as kv_create
    from geomx_tpu.trainer_device import DeviceResidentTrainer

    n = (1 << 24) + 64          # would have raised pre-fix
    spike = (1 << 24) + 37      # not representable in a f32 mantissa +1

    def grad_fn(leaves, X, y):
        w = leaves[0]
        g = jnp.zeros_like(w).at[spike].set(100.0).at[3].set(-50.0)
        return jnp.sum(w * 0.0), [g]

    kv = kv_create("local")
    tr = DeviceResidentTrainer(
        [np.zeros(n, np.float32)], kv, grad_fn,
        threshold=2 / n, learning_rate=0.1)
    tr.step(jnp.zeros(()), None)
    w = tr.leaves[0]
    nz = np.nonzero(w)[0]
    np.testing.assert_array_equal(nz, [3, spike])
    np.testing.assert_allclose(w[spike], -10.0)   # -lr * 100
    np.testing.assert_allclose(w[3], 5.0)         # -lr * -50


def test_sparse_payload_duplicate_indices_sum():
    """A payload carrying the same index twice aggregates by SUM (the
    documented contract; fancy-index assignment would silently drop
    the first value): in the decompressor, and through the round."""
    from geomx_tpu.compression import _generic_decompress

    vals = np.array([1.0, 2.0, 5.0], np.float32)
    out = _generic_decompress("bsc", vals, np.array([5, 5, 0], np.int32), 8)
    np.testing.assert_allclose(out[[0, 5]], [5.0, 3.0])
    assert out.sum() == 8.0

    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    try:
        def master_init(kv):
            kv.init(3, np.zeros(8, np.float32))
            kv.wait()

        def worker(kv):
            kv.init(3, np.zeros(8, np.float32))
            kv.wait()
            avals, aidx = kv.push_pull_bsc_batch(
                [3], [vals], [np.array([5, 5, 0], np.int64)])()[3]
            dense = np.zeros(8, np.float32)
            dense[aidx] = avals
            # two workers, each 1 + 2 at index 5 and 5 at index 0
            np.testing.assert_array_equal(dense, 2 * out)

        _run_workers(topo, worker, master_init)
    finally:
        topo.stop()


@pytest.mark.parametrize("sharded", [False, True])
def test_push_pull_bsc_batch_sums_keys_across_shards(sharded):
    """The combined sparse round over several keys must return the
    numpy sum of the workers' selections — including keys partitioned
    across server shards (per-rank slices of one batch, multi-rank
    ack/data accounting)."""
    n0, n1 = 40, 24
    kw = dict(num_parties=2, workers_per_party=1)
    if sharded:
        kw.update(servers_per_party=2, bigarray_bound=16)
    topo = InProcessHiPS(**kw).start()
    results = {}
    try:
        def master_init(kv):
            kv.init(0, np.zeros(n0, np.float32))
            kv.init(1, np.zeros(n1, np.float32))
            kv.wait()

        def worker(kv):
            widx = 0 if kv is topo.workers[0] else 1
            for k, n in ((0, n0), (1, n1)):
                kv.init(k, np.zeros(n, np.float32))
                kv.pull(k, out=np.zeros(n, np.float32))
            kv.wait()
            if widx == 0:
                sels = {0: (np.array([1.0, 2.0], np.float32),
                            np.array([0, 33], np.int64)),
                        1: (np.array([5.0], np.float32),
                            np.array([17], np.int64))}
            else:
                sels = {0: (np.array([10.0, 20.0], np.float32),
                            np.array([33, 39], np.int64)),
                        1: (np.array([7.0, 8.0], np.float32),
                            np.array([17, 3], np.int64))}
            agg = kv.push_pull_bsc_batch(
                [0, 1], [sels[0][0], sels[1][0]],
                [sels[0][1], sels[1][1]])()
            dense = {}
            for k, n in ((0, n0), (1, n1)):
                d = np.zeros(n, np.float32)
                avals, aidx = agg[k]
                d[aidx] = avals
                dense[k] = d
            results[widx] = dense

        _run_workers(topo, worker, master_init)
    finally:
        topo.stop()

    e0 = np.zeros(n0, np.float32)
    e0[[0, 33]] += [1.0, 2.0]
    e0[[33, 39]] += [10.0, 20.0]
    e1 = np.zeros(n1, np.float32)
    e1[[17]] += [5.0]
    e1[[17, 3]] += [7.0, 8.0]
    for w in (0, 1):
        np.testing.assert_allclose(results[w][0], e0)
        np.testing.assert_allclose(results[w][1], e1)


@pytest.mark.parametrize("server_compression", [
    None, {"type": "bsc", "threshold": 1.0}], ids=["dense_wan", "bsc_wan"])
def test_wan_retry_resends_the_same_bytes(server_compression):
    """Two workers a party, and party 0's first WAN forward is given up
    on (as the resender reports it): the per-slice retry of the SAME
    cycle must carry the bytes of the first attempt (a sparse forward's
    positions in their coded form), and the aggregate must be the numpy
    sum. The party server keeps the array its
    decompressor built as the round's accumulator and stages it for the
    WAN without copying it; the second worker's push adds into it in
    place; a retry that re-encoded, or a forward that saw a later write,
    would show here."""
    sizes = {0: 40, 1: 24}
    topo = InProcessHiPS(num_parties=2, workers_per_party=2).start()
    srv = next(s for s in topo.servers if s.worker_global is not None)
    wg, van = srv.worker_global, srv.po_global.van
    real_push, real_send = wg.push, van.send
    forwards = []          # per WAN push: {(key, lo): (vals, aux) bytes}
    positions = []         # the type of every positions part forwarded
    drop = []

    def push(kvs, rank, **kw):
        positions.extend(np.asarray(a).dtype for a in kvs.aux
                         if a is not None)
        forwards.append({
            (k, kvs.offset_of(i)): (
                np.asarray(kvs.vals[i]).tobytes(),
                None if kvs.aux[i] is None
                else np.asarray(kvs.aux[i]).tobytes())
            for i, k in enumerate(kvs.keys)})
        if len(forwards) == 1:
            drop.append(True)
        return real_push(kvs, rank, **kw)

    def send(msg):
        if drop:
            drop.clear()
            threading.Thread(
                target=van.give_up_handler,
                args=(msg, RuntimeError, "dropped by the test"),
                daemon=True).start()
            return
        real_send(msg)

    wg.push, van.send = push, send
    rng = np.random.default_rng(5)
    sels = [{k: (rng.integers(1, 9, 4).astype(np.float32),
                 rng.choice(n, 4, replace=False).astype(np.int64))
             for k, n in sizes.items()} for _ in range(4)]
    results = {}
    try:
        def master_init(kv):
            if server_compression:
                kv.set_gradient_compression(dict(server_compression))
            for k, n in sizes.items():
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        def worker(kv):
            widx = topo.workers.index(kv)
            for k, n in sizes.items():
                kv.init(k, np.zeros(n, np.float32))
                kv.pull(k, out=np.zeros(n, np.float32))
            kv.wait()
            agg = kv.push_pull_bsc_batch(
                list(sizes), [sels[widx][k][0] for k in sizes],
                [sels[widx][k][1] for k in sizes])()
            results[widx] = {}
            for k, n in sizes.items():
                results[widx][k] = np.zeros(n, np.float32)
                avals, aidx = agg[k]
                results[widx][k][aidx] = avals

        _run_workers(topo, worker, master_init)
    finally:
        topo.stop()

    first, resent = forwards[0], {}
    for f in forwards[1:]:
        resent.update(f)
    assert len(first) == len(sizes) and resent == first
    # a sparse forward's positions cross coded, and the retry resends
    # the coded bytes it kept (st.fwd_wire), not a second encoding
    assert (positions == [np.uint8] * 4 if server_compression
            else not positions)
    for k, n in sizes.items():
        expect = np.zeros(n, np.float32)
        for sel in sels:
            np.add.at(expect, sel[k][1], sel[k][0])
        for widx in range(4):
            np.testing.assert_array_equal(results[widx][k], expect)
