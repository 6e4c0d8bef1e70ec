"""Batched multi-key push/pull wire (list forms of push/pull).

One message per server per round instead of one per key: the server
runs its per-key state machines unchanged and a countdown responder
(kvstore.server._BatchResponder) merges their acks/responses into the
single response the transport allows per request. Semantics must equal
the per-key wire exactly, including the push-ack -> pull freshness
ordering.
"""

import numpy as np
import pytest

from geomx_tpu.optimizer import SGD
from geomx_tpu.simulate import InProcessHiPS

KEYS = list(range(6))
SHAPES = [(4,), (2, 3), (8,), (5,), (1,), (7,)]


def _run(batched, sharded: bool = False):
    kw = dict(num_parties=2, workers_per_party=1)
    if sharded:
        kw.update(servers_per_party=2, bigarray_bound=4)
    topo = InProcessHiPS(**kw).start()
    result = {}
    try:
        def master_init(kv):
            kv.set_optimizer(SGD(learning_rate=0.5))
            for k, sh in zip(KEYS, SHAPES):
                kv.init(k, np.zeros(sh, np.float32))
            kv.wait()

        def worker(kv):
            widx = 0 if kv is topo.workers[0] else 1
            outs = [np.zeros(sh, np.float32) for sh in SHAPES]
            for k, o in zip(KEYS, outs):
                kv.init(k, o.copy())
                kv.pull(k, out=o)
            kv.wait()
            rng = np.random.RandomState(17)  # same on both workers
            for step in range(3):
                grads = [rng.uniform(-1, 1, sh).astype(np.float32) / 2
                         for sh in SHAPES]
                if batched == "push_pull":
                    kv.push_pull(KEYS, grads, out=outs)
                elif batched:
                    kv.push(KEYS, grads)
                    kv.pull(KEYS, out=outs)
                else:
                    for k, g, o in zip(KEYS, grads, outs):
                        kv.push(k, g)
                        kv.pull(k, out=o)
                kv.wait()
            result[widx] = [o.copy() for o in outs]

        topo.run_workers(worker, include_master=master_init, timeout=300)
    finally:
        topo.stop()
    np.testing.assert_equal(len(result), 2)
    for a, b in zip(result[0], result[1]):
        np.testing.assert_array_equal(a, b)
    return result[0]


@pytest.mark.parametrize("sharded", [False, True])
def test_batched_wire_matches_per_key_exactly(sharded):
    """Same seeds, same optimizer: the batched rounds must produce
    bit-identical weights to per-key rounds (freshness ordering and
    aggregation semantics preserved)."""
    per_key = _run(batched=False, sharded=sharded)
    batched = _run(batched=True, sharded=sharded)
    for a, b in zip(per_key, batched):
        np.testing.assert_array_equal(a, b)
    # and training actually moved the weights
    assert any(np.abs(a).sum() > 0 for a in batched)


@pytest.mark.parametrize("sharded", [False, True])
def test_push_pull_matches_per_key_exactly(sharded):
    """Combined push+pull (ZPushPull wire: the round's ack carries the
    post-round params) must be bit-identical to the two-op sequence."""
    per_key = _run(batched=False, sharded=sharded)
    combined = _run(batched="push_pull", sharded=sharded)
    for a, b in zip(per_key, combined):
        np.testing.assert_array_equal(a, b)
    assert any(np.abs(a).sum() > 0 for a in combined)


def test_batched_pull_requires_writable_arrays():
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    try:
        def master_init(kv):
            for k in (0, 1):
                kv.init(k, np.zeros(3, np.float32))
            kv.wait()

        def worker(kv):
            for k in (0, 1):
                kv.init(k, np.zeros(3, np.float32))
            kv.wait()
            with pytest.raises(TypeError, match="writable"):
                kv.pull([0, 1], out=[np.zeros(3), "nope"])

        topo.run_workers(worker, include_master=master_init, timeout=120)
    finally:
        topo.stop()


def test_duplicate_keys_rejected_loudly():
    """Review finding: duplicate keys in one list call would corrupt
    the batched bookkeeping — and even the per-key path double-counts
    the worker's FSA contribution and wedges the round barrier. The
    misuse is rejected with an error, never a hang."""
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    try:
        def master_init(kv):
            kv.init(0, np.zeros(3, np.float32))
            kv.wait()

        def worker(kv):
            kv.init(0, np.zeros(3, np.float32))
            kv.wait()
            with pytest.raises(ValueError, match="duplicate keys"):
                kv.push([0, 0], [np.ones(3, np.float32),
                                 np.ones(3, np.float32)])
            with pytest.raises(ValueError, match="duplicate keys"):
                kv.pull([0, 0], out=[np.zeros(3, np.float32),
                                     np.zeros(3, np.float32)])

        topo.run_workers(worker, include_master=master_init, timeout=120)
    finally:
        topo.stop()


def test_p3_list_form_fans_out_per_key():
    """Under ENABLE_P3 the list forms fan out to per-key prioritized
    messages (coalescing would defeat the priority send thread); the
    results must still be exact, and the sparse batch paths must fan
    out the same way."""
    topo = InProcessHiPS(num_parties=2, workers_per_party=1,
                         extra_cfg={"enable_p3": True,
                                    "bigarray_bound": 8}).start()
    try:
        def master_init(kv):
            kv.set_optimizer(SGD(learning_rate=1.0))
            for k, n in ((0, 20), (1, 6)):
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        def worker(kv):
            assert kv.cfg.enable_p3
            outs = [np.zeros(20, np.float32), np.zeros(6, np.float32)]
            for k, o in zip((0, 1), outs):
                kv.init(k, o.copy())
                kv.pull(k, out=o)
            kv.wait()
            for r in range(1, 3):
                kv.push([0, 1], [np.ones(20, np.float32),
                                 np.ones(6, np.float32)])
                kv.pull([0, 1], out=outs)
                kv.wait()
                for o in outs:
                    np.testing.assert_allclose(o, -2.0 * r)

        topo.run_workers(worker, include_master=master_init, timeout=300)
    finally:
        topo.stop()


def test_p3_sparse_round_sums_and_chunks_per_key():
    """The sparse round under ENABLE_P3 (aggregator mode: no server
    optimizer, the ack is the aggregated selection): two workers'
    entries sum, and with a P3_SLICE_BYTES that makes each key its own
    chunk the round sends one message per key per server at descending
    priority — chunks are this round's P3."""
    topo = InProcessHiPS(num_parties=2, workers_per_party=1,
                         extra_cfg={"enable_p3": True,
                                    "bigarray_bound": 8,
                                    "p3_slice_bytes": 8}).start()
    sent = {}
    try:
        def master_init(kv):
            for k, n in ((0, 20), (1, 6)):
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        def worker(kv):
            assert kv.cfg.enable_p3
            for k, n in ((0, 20), (1, 6)):
                kv.init(k, np.zeros(n, np.float32))
                kv.pull(k, out=np.zeros(n, np.float32))
            kv.wait()
            sel = ([0, 1], [np.array([1.0], np.float32)] * 2,
                   [np.array([3], np.int64)] * 2)

            def check(agg):
                for k in (0, 1):
                    avals, aidx = agg[k]
                    dense = np.zeros(20 if k == 0 else 6, np.float32)
                    dense[aidx] = avals
                    np.testing.assert_allclose(dense[3], 2.0)  # 2 workers

            check(kv.push_pull_bsc_batch(*sel)())
            # the chunked form: 8 wire bytes a selected element, one
            # element a key, so a budget of 8 bytes is a key a chunk
            log = sent.setdefault(id(kv), [])
            real_push = kv.kvw.push

            def push(kvs, rank, **kw):
                log.append((tuple(kvs.keys), rank, kw["priority"]))
                return real_push(kvs, rank, **kw)

            kv.kvw.push = push
            try:
                check(kv.push_pull_bsc_batch_async(*sel, priority=7)
                      .results(timeout=120))
            finally:
                del kv.kvw.push

        topo.run_workers(worker, include_master=master_init, timeout=300)
    finally:
        topo.stop()
    assert len(sent) == 2
    for log in sent.values():
        # every message carries ONE key (possibly several of its shards
        # for one server), and key 1's chunk rides one priority lower
        assert log and all(len(set(keys)) == 1 for keys, _r, _p in log)
        assert {(keys[0], prio) for keys, _r, prio in log} \
            == {(0, 7), (1, 6)}
        per_key_server = [(keys[0], rank) for keys, rank, _p in log]
        assert len(per_key_server) == len(set(per_key_server))
