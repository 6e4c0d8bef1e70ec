"""Pipelined round: async chunked push_pull (P3 slicing).

The async frontier (kvstore.frontier) splits a round into
priority-ordered chunks and completes keys as their responses land;
the acceptance bar is BIT-exactness against the serial wire — same
FSA rounds, same aggregation, same post-round bytes — with only the
blocking moved. Covers the planning/future primitives, the dense and
BSC async wire against their blocking twins (including multi-shard
keys under P3_SLICE_BYTES sharding), the pipelined device trainer,
and out-of-order completion under a seeded FaultPlan.
"""

import json
import threading
import time

import numpy as np
import pytest

from geomx_tpu.kvstore.frontier import (RoundFuture, give_up_exc,
                                        plan_chunks)
from geomx_tpu.optimizer import SGD
from geomx_tpu.simulate import InProcessHiPS

KEYS = list(range(6))
SHAPES = [(4,), (2, 3), (8,), (5,), (1,), (7,)]


# ---------------------------------------------------------------------------
# chunk planning
# ---------------------------------------------------------------------------

def test_plan_chunks_groups_in_layer_order():
    chunks = plan_chunks(["a", "b", "c", "d"], [4, 4, 4, 4], 8)
    assert [c.items for c in chunks] == [["a", "b"], ["c", "d"]]
    assert [c.cid for c in chunks] == [0, 1]
    # chunk index descends into priority: layer order = priority
    assert [c.priority for c in chunks] == [0, -1]


def test_plan_chunks_zero_budget_is_one_chunk():
    chunks = plan_chunks([1, 2, 3], [100, 200, 300], 0, base_priority=5)
    assert len(chunks) == 1
    assert chunks[0].items == [1, 2, 3]
    assert chunks[0].priority == 5


def test_plan_chunks_oversized_item_gets_own_chunk():
    # an item above the budget is NOT split (BSC keys stay whole for
    # the server FSA's per-(key, shard) push counting)
    chunks = plan_chunks(["small", "huge", "small2"], [2, 99, 2], 8)
    assert [c.items for c in chunks] == [["small", "huge"], ["small2"]] \
        or [c.items for c in chunks] == [["small"], ["huge"], ["small2"]]
    # greedy close: "huge" may close the first chunk or own one, but
    # never merges with items AFTER it beyond the budget
    assert all(sum({"small": 2, "huge": 99, "small2": 2}[i]
                   for i in c.items) <= 101 for c in chunks)


def test_plan_chunks_empty():
    assert plan_chunks([], [], 8) == []


def test_plan_chunks_base_priority_offsets_every_chunk():
    chunks = plan_chunks([0, 1, 2], [8, 8, 8], 8, base_priority=-3)
    assert [c.priority for c in chunks] == [-3, -4, -5]


# ---------------------------------------------------------------------------
# RoundFuture
# ---------------------------------------------------------------------------

def test_round_future_completes_per_key():
    fut = RoundFuture([1, 2])
    assert not fut.done()
    fut.complete_key(1, "r1")
    assert fut.done([1]) and not fut.done()
    assert fut.result(1, timeout=1) == "r1"
    fut.complete_key(2, "r2")
    assert fut.results(timeout=1) == {1: "r1", 2: "r2"}
    # idempotent: a duplicate completion does not clobber the result
    fut.complete_key(1, "other")
    assert fut.result(1) == "r1"


def test_round_future_wait_timeout_lists_pending():
    fut = RoundFuture([3, 4])
    fut.complete_key(3)
    with pytest.raises(TimeoutError, match=r"\[4\]"):
        fut.wait(timeout=0.05)


def test_round_future_on_key_fires_now_and_later():
    fut = RoundFuture([1, 2])
    seen = []
    fut.on_key(1, seen.append)
    fut.complete_key(1)
    fut.on_key(1, seen.append)    # already done: fires immediately
    assert seen == [1, 1]


def test_round_future_rejects_duplicate_keys():
    with pytest.raises(AssertionError, match="duplicate"):
        RoundFuture([1, 1])


def test_round_future_error_mapping_and_consume():
    # a blown resend deadline maps to TimeoutError; other give-ups stay
    # RuntimeError — same classes KVStoreDist.wait() raises
    assert give_up_exc(["delivery deadline exceeded"]) is TimeoutError
    assert give_up_exc(["retry cap"]) is RuntimeError

    consumed = []
    fut = RoundFuture([1], consume=consumed.extend)
    fut.add_error(1, "push key 1: delivery deadline exceeded")
    fut.complete_key(1)
    with pytest.raises(TimeoutError, match="delivery deadline"):
        fut.wait(timeout=1)
    assert consumed == ["push key 1: delivery deadline exceeded"]

    fut2 = RoundFuture([7])
    fut2.add_error(7, "gave up after 5 retries")
    fut2.complete_key(7)
    with pytest.raises(RuntimeError, match="retries"):
        fut2.wait(timeout=1)


def test_round_future_completion_from_other_thread():
    fut = RoundFuture([9])
    t = threading.Timer(0.05, fut.complete_key, args=(9, "late"))
    t.start()
    assert fut.result(9, timeout=5) == "late"


# ---------------------------------------------------------------------------
# OpFuture (kv_app-level handle)
# ---------------------------------------------------------------------------

class _FakeWorker:
    def __init__(self, failure=None, resp=()):
        self._failure = failure
        self._resp = list(resp)

    def take_failure(self, ts):
        return self._failure

    def take_response(self, ts):
        return self._resp


def test_op_future_completes_and_serves_response():
    from geomx_tpu.ps.kv_app import OpFuture

    fut = OpFuture(_FakeWorker(resp=["kvs"]), 3)
    assert not fut.done()
    fut._fire(3)
    fut.wait(timeout=1)
    assert fut.done() and fut.failure() is None
    assert fut.responses() == ["kvs"]


def test_op_future_raises_give_up_with_class_mapping():
    from geomx_tpu.ps.kv_app import OpFuture

    fut = OpFuture(_FakeWorker(failure="delivery deadline exceeded"), 5)
    fut._fire(5)
    with pytest.raises(TimeoutError, match="delivery deadline"):
        fut.wait(timeout=1)

    fut2 = OpFuture(_FakeWorker(failure="gave up after retries"), 6)
    fut2._fire(6)
    with pytest.raises(RuntimeError, match="gave up"):
        fut2.wait(timeout=1)

    fut3 = OpFuture(_FakeWorker(), 7)
    with pytest.raises(TimeoutError, match="still pending"):
        fut3.wait(timeout=0.05)


# ---------------------------------------------------------------------------
# dense async wire == serial wire, bit for bit
# ---------------------------------------------------------------------------

def _run_dense(mode, slice_bytes=0, sharded=False, extra_cfg=None):
    kw = dict(num_parties=2, workers_per_party=1)
    if sharded:
        kw.update(servers_per_party=2, bigarray_bound=4)
    if extra_cfg:
        kw["extra_cfg"] = extra_cfg
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
                if mode == "async":
                    fut = kv.push_pull_async(KEYS, grads, outs,
                                             slice_bytes=slice_bytes)
                    fut.wait(timeout=120)
                else:
                    kv.push_pull(KEYS, grads, out=outs)
                    kv.wait()
            result[widx] = [o.copy() for o in outs]

        topo.run_workers(worker, include_master=master_init, timeout=300)
    finally:
        topo.stop()
    np.testing.assert_equal(len(result), 2)
    for a, b in zip(result[0], result[1]):
        np.testing.assert_array_equal(a, b)
    return result[0]


@pytest.mark.parametrize("slice_bytes", [0, 16, 10 ** 6])
def test_push_pull_async_matches_serial_exactly(slice_bytes):
    """Chunked async rounds must be bit-identical to the blocking
    combined wire at every chunk budget (one chunk, many chunks, one
    chunk again via a huge budget)."""
    serial = _run_dense("serial")
    piped = _run_dense("async", slice_bytes=slice_bytes)
    for a, b in zip(serial, piped):
        np.testing.assert_array_equal(a, b)
    assert any(np.abs(a).sum() > 0 for a in piped)


def test_push_pull_async_matches_serial_sharded():
    """Chunks at _shards() granularity across 2 servers per party."""
    serial = _run_dense("serial", sharded=True)
    piped = _run_dense("async", slice_bytes=16, sharded=True)
    for a, b in zip(serial, piped):
        np.testing.assert_array_equal(a, b)


def test_push_pull_async_p3_slice_bytes_sharding():
    """P3_SLICE_BYTES > 0 slices keys into priority shards at init
    (sharding.assign_p3); the async round and the serial round must
    still agree bit for bit — this is the multi-(key, off)-per-message
    path through the server's batched WAN forward."""
    cfg = {"p3_slice_bytes": 16}
    serial = _run_dense("serial", extra_cfg=cfg)
    piped = _run_dense("async", slice_bytes=16, extra_cfg=cfg)
    for a, b in zip(serial, piped):
        np.testing.assert_array_equal(a, b)


def test_push_pull_async_rejects_bad_inputs():
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    try:
        def master_init(kv):
            kv.init(0, np.zeros(3, np.float32))
            kv.wait()

        def worker(kv):
            kv.init(0, np.zeros(3, np.float32))
            kv.wait()
            g = np.ones(3, np.float32)
            with pytest.raises(ValueError, match="duplicate"):
                kv.push_pull_async([0, 0], [g, g],
                                   [np.zeros(3, np.float32),
                                    np.zeros(3, np.float32)])
            with pytest.raises(TypeError, match="writable"):
                kv.push_pull_async([0], [g], ["nope"])

        topo.run_workers(worker, include_master=master_init, timeout=120)
    finally:
        topo.stop()


# ---------------------------------------------------------------------------
# BSC async wire == blocking BSC join, element for element
# ---------------------------------------------------------------------------

def _run_bsc(mode, slice_bytes=0, extra_cfg=None):
    sizes = [8, 5, 12, 6]
    keys = list(range(len(sizes)))
    kw = dict(num_parties=2, workers_per_party=1)
    if extra_cfg:
        kw["extra_cfg"] = extra_cfg
    topo = InProcessHiPS(**kw).start()
    result = {}
    try:
        def master_init(kv):
            for k, n in zip(keys, sizes):
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        def worker(kv):
            widx = 0 if kv is topo.workers[0] else 1
            for k, n in zip(keys, sizes):
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()
            rng = np.random.RandomState(5 + widx)
            vals = [rng.rand(3).astype(np.float32) + 1.0 for _ in keys]
            idxs = [np.sort(rng.choice(n, 3, replace=False))
                    for n in sizes]
            if mode == "async":
                fut = kv.push_pull_bsc_batch_async(
                    keys, vals, idxs, slice_bytes=slice_bytes)
                agg = fut.results(timeout=120)
            else:
                agg = kv.push_pull_bsc_batch(keys, vals, idxs)()
            # compare as dense scatters: part ORDER may differ between
            # the chunked and monolithic responses, the bytes must not
            dense = {}
            for k, n in zip(keys, sizes):
                buf = np.zeros(n, np.float32)
                avals, aidx = agg[k]
                np.add.at(buf, aidx, avals)
                dense[k] = buf
            result[widx] = dense

        topo.run_workers(worker, include_master=master_init, timeout=300)
    finally:
        topo.stop()
    np.testing.assert_equal(len(result), 2)
    for k in keys:
        np.testing.assert_array_equal(result[0][k], result[1][k])
    return result[0]


@pytest.mark.parametrize("slice_bytes", [0, 48])
def test_bsc_async_matches_blocking_join(slice_bytes):
    blocking = _run_bsc("sync")
    piped = _run_bsc("async", slice_bytes=slice_bytes)
    for k in blocking:
        np.testing.assert_array_equal(blocking[k], piped[k])
    assert any(np.abs(v).sum() > 0 for v in piped.values())


def test_bsc_async_under_p3_slice_sharding():
    """Keys sliced into multiple tiny shards per server (the
    P3_SLICE_BYTES _shards branch): the combined BSC round must still
    aggregate exactly — covers >1 entry of the SAME key per message on
    both tiers, and the batched global forward's overlap routing."""
    cfg = {"p3_slice_bytes": 8}
    blocking = _run_bsc("sync", extra_cfg=cfg)
    piped = _run_bsc("async", slice_bytes=24, extra_cfg=cfg)
    for k in blocking:
        np.testing.assert_array_equal(blocking[k], piped[k])


# ---------------------------------------------------------------------------
# out-of-order completion under faults (chaos tier)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_async_frontier_exact_under_faultplan():
    """Drop + reorder + dup on every link (seeded), resend on: chunk
    responses land out of order and some messages retransmit, yet the
    per-key async results are bit-identical to a clean serial round.
    Also asserts the frontier completes every key exactly once."""
    plan = json.dumps({"rules": [
        {"type": "drop", "p": 0.15},
        {"type": "dup", "p": 0.15},
        {"type": "reorder", "window": 4},
    ]})
    chaos_cfg = {"fault_plan": plan, "ps_seed": 7, "resend": True,
                 "resend_timeout_ms": 20}

    clean = _run_bsc("sync")
    completions = []

    sizes = [8, 5, 12, 6]
    keys = list(range(len(sizes)))
    topo = InProcessHiPS(num_parties=2, workers_per_party=1,
                         extra_cfg=chaos_cfg).start()
    result = {}
    try:
        def master_init(kv):
            for k, n in zip(keys, sizes):
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        def worker(kv):
            widx = 0 if kv is topo.workers[0] else 1
            for k, n in zip(keys, sizes):
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()
            rng = np.random.RandomState(5 + widx)
            vals = [rng.rand(3).astype(np.float32) + 1.0 for _ in keys]
            idxs = [np.sort(rng.choice(n, 3, replace=False))
                    for n in sizes]
            fut = kv.push_pull_bsc_batch_async(keys, vals, idxs,
                                               slice_bytes=24)
            for k in keys:
                fut.on_key(k, lambda kk: completions.append(kk))
            agg = fut.results(timeout=120)
            dense = {}
            for k, n in zip(keys, sizes):
                buf = np.zeros(n, np.float32)
                avals, aidx = agg[k]
                np.add.at(buf, aidx, avals)
                dense[k] = buf
            result[widx] = dense

        topo.run_workers(worker, include_master=master_init, timeout=300)
    finally:
        topo.stop()

    for k in keys:
        np.testing.assert_array_equal(result[0][k], result[1][k])
        np.testing.assert_array_equal(result[0][k], clean[k])
    # every key completed on both workers, each exactly once
    assert sorted(completions) == sorted(keys * 2)


# ---------------------------------------------------------------------------
# device trainer: the chunking of the round does not change its result
# ---------------------------------------------------------------------------

def _run_trainer(extra_cfg, rounds=8, look=None):
    import jax.numpy as jnp

    from geomx_tpu.trainer_device import DeviceResidentTrainer

    target = np.arange(1.0, 9.0, dtype=np.float32).reshape(2, 4)

    def loss_fn(leaves, X, y):
        diff = leaves[0] - jnp.asarray(target) + X
        return (0.5 * jnp.sum(diff * diff) + jnp.sum(leaves[1] ** 2),
                [diff, 2.0 * leaves[1] + 1.0])

    topo = InProcessHiPS(num_parties=2, workers_per_party=1,
                         extra_cfg=extra_cfg).start()
    results = {}
    try:
        def master_init(kv):
            kv.init(0, np.zeros((2, 4), np.float32))
            kv.init(1, np.zeros((5,), np.float32))
            kv.wait()

        def worker(kv):
            widx = 0 if kv is topo.workers[0] else 1
            tr = DeviceResidentTrainer(
                [np.zeros((2, 4), np.float32),
                 np.zeros((5,), np.float32)],
                kv, loss_fn, threshold=0.5, learning_rate=0.2,
                momentum=0.9)
            shift = jnp.asarray(0.5 if widx == 0 else -0.5)
            for _ in range(rounds):
                tr.step(shift, None)
            results[widx] = ([np.asarray(l).copy() for l in tr.leaves],
                             len(tr._chunks))
            if look is not None:
                look(kv, tr)

        topo.run_workers(worker, include_master=master_init,
                         timeout=300)
    finally:
        topo.stop()
    (l0, nch0), (l1, nch1) = results[0], results[1]
    assert nch0 == nch1
    for a, b in zip(l0, l1):
        np.testing.assert_array_equal(a, b)
    return l0, nch0


def test_trainer_round_is_chunking_invariant():
    """P3_SLICE_BYTES cuts DeviceResidentTrainer's round into chunks
    that are fetched, sent and applied independently; the post-training
    leaves must equal the one-chunk round's bit for bit (chunk flat
    ranges partition the parameter vector; per-coordinate arithmetic is
    unchanged)."""
    one, nchunks = _run_trainer({"p3_slice_bytes": 0})
    assert nchunks == 1
    two, nchunks = _run_trainer({"p3_slice_bytes": 8})
    assert nchunks == 2
    for a, b in zip(one, two):
        np.testing.assert_array_equal(a, b)
    assert any(np.abs(a).sum() > 0 for a in two)


# ---------------------------------------------------------------------------
# device trainer: where nothing was asked for, the round is cut at the
# declared party-global link's bandwidth-delay product
# ---------------------------------------------------------------------------

WAN_50MS_100MBIT = json.dumps(
    {"default": {"tier": "global", "rtt_ms": 50.0, "bw_mbps": 100.0}})
# 75,000 elements at threshold 0.5: 37,500 entries, 300,000 wire bytes
TOY_SHAPES = [(75000,), (75000,), (75000,), (75000,), (300,), (75000,)]


def _toy_trainer(cfg):
    """A trainer over the local store, which answers a round with the
    selection itself, under ``cfg`` as its store's configuration
    (``None``: the local store as it comes, with none)."""
    import jax.numpy as jnp

    from geomx_tpu.kvstore import create as kv_create
    from geomx_tpu.trainer_device import DeviceResidentTrainer

    kv = kv_create("local")
    if cfg is not None:
        kv.cfg = cfg
    return DeviceResidentTrainer(
        [np.ones(s, np.float32) for s in TOY_SHAPES], kv,
        lambda leaves, X, y: (sum(jnp.sum(l * l) for l in leaves) * X,
                              [2 * l * X for l in leaves]),
        threshold=0.5, learning_rate=0.1)


def _plan(*budgets):
    """The toy keys' cut at each budget, as ``plan_chunks`` says."""
    wire = [8 * max(int(np.prod(s) * 0.5), 1) for s in TOY_SHAPES]
    return [[c.items for c in plan_chunks(list(range(len(wire))), wire, b)]
            for b in budgets]


def test_trainer_cuts_its_round_at_the_declared_links_bdp():
    """50 ms x 100 Mbit/s is 625,000 bytes: two toy keys a chunk, where
    the floor of 65,536 would give each its own and 0 one for all."""
    from geomx_tpu.config import Config
    from geomx_tpu.kvstore.frontier import slice_bytes_from_shape

    cfg = Config(shape_plan=WAN_50MS_100MBIT)
    assert slice_bytes_from_shape(cfg) == 625000
    at_bdp, at_floor, whole = _plan(625000, 65536, 0)
    assert at_bdp == [[0, 1], [2, 3, 4], [5]]
    assert len(at_floor) == 6 and len(whole) == 1
    tr = _toy_trainer(cfg)
    assert [c.items for c in tr._chunks] == at_bdp
    assert cfg.p3_slice_bytes == 0     # the plan's alone: not written back
    # every chunk is one flat slice, its upload sized to its own keys
    assert [m[3] for m in tr._chunk_meta] == [150000, 150300, 75000]
    assert float(tr.step(np.float32(1.0), None)) > 0


@pytest.mark.parametrize("cfg_kw", [
    None,                               # a local store: no configuration
    {},                                 # no plan
    {"shape_plan": json.dumps(          # a plan with no global link
        {"default": {"tier": "local", "rtt_ms": 50.0, "bw_mbps": 100.0}})},
    {"shape_plan": json.dumps({"links": []})},
], ids=["local-store", "no-plan", "lan-only-plan", "empty-plan"])
def test_trainer_round_is_one_chunk_without_a_declared_global_link(cfg_kw):
    from geomx_tpu.config import Config

    tr = _toy_trainer(None if cfg_kw is None else Config(**cfg_kw))
    assert [c.items for c in tr._chunks] == _plan(0)[0]


@pytest.mark.parametrize("asked", [300000, 4 << 20])
def test_an_explicit_budget_wins_over_the_declared_link(asked):
    from geomx_tpu.config import Config

    tr = _toy_trainer(Config(shape_plan=WAN_50MS_100MBIT,
                             p3_slice_bytes=asked))
    assert [c.items for c in tr._chunks] == _plan(asked)[0]
    assert _plan(asked) != _plan(625000)


def test_the_declared_link_cuts_the_round_and_shards_no_key():
    """Under a plan the trainer's round is in chunks, and that is all:
    the store's configuration holds the budget it held, and every key
    has the shards ``KVStoreDist._shards`` gives without one, where
    ``P3_SLICE_BYTES`` slices them at the budget."""
    from geomx_tpu.kvstore import sharding

    seen = {}

    def look(kv, tr):
        seen[id(kv)] = (
            kv.cfg.p3_slice_bytes, len(tr._chunks),
            {k: [(s.server_rank, s.offset, s.length) for s in i.shards]
             for k, i in kv._key_info.items()},
            {k: [(s.server_rank, s.offset, s.length) for s in
                 sharding.assign(k, i.total, kv.po.num_servers,
                                 kv.cfg.bigarray_bound)]
             for k, i in kv._key_info.items()})

    plan = json.dumps({"default": {"tier": "global", "rtt_ms": 2.0,
                                   "bw_mbps": 1000.0}})
    shaped, nchunks = _run_trainer({"shape_plan": plan}, rounds=3,
                                   look=look)
    assert nchunks == 1     # 250,000 bytes: the model's 72 fit one chunk
    assert len(seen) == 2
    for budget, _n, shards, unsliced in seen.values():
        assert budget == 0
        assert shards == unsliced and set(shards) == {0, 1}
    seen.clear()
    sliced, nchunks = _run_trainer({"p3_slice_bytes": 8}, rounds=3,
                                   look=look)
    assert nchunks == 2
    for budget, _n, shards, unsliced in seen.values():
        assert budget == 8 and shards != unsliced
    for a, b in zip(shaped, sliced):
        np.testing.assert_array_equal(a, b)


BSC_SHAPES = [(8192,), (64, 128), (8000,), (90, 90), (8192,), (33,),
              (8192,)]


def _bsc_leaves():
    return [np.random.RandomState(3 + i).uniform(-1, 1, s)
            .astype(np.float32) for i, s in enumerate(BSC_SHAPES)]


def _run_bsc_trainer(extra_cfg, workers_per_party, rounds=6):
    """``rounds`` rounds of the device trainer on 2 parties whose
    servers re-select with Bi-Sparse for the party-global hop ->
    (worker 0's leaves, chunks a round, van messages a round over the
    rounds after the first)."""
    import jax.numpy as jnp

    from geomx_tpu import telemetry
    from geomx_tpu.trainer_device import DeviceResidentTrainer

    leaves0 = _bsc_leaves()

    def loss_fn(leaves, X, y):
        # every worker's gradient its own, every coordinate's its own
        return (sum(jnp.sum(jnp.sin(l * X) ** 2) for l in leaves),
                [2 * jnp.sin(l * X) * jnp.cos(l * X) * X for l in leaves])

    topo = InProcessHiPS(num_parties=2,
                         workers_per_party=workers_per_party,
                         extra_cfg=extra_cfg).start()
    n = 2 * workers_per_party
    meet = threading.Barrier(n)
    results, sent = {}, []

    def messages():
        return sum(v for k, v in telemetry.snapshot()["counters"].items()
                   if k.startswith("van.messages_sent"))

    was_on = telemetry.enabled()
    telemetry.enable(True)
    try:
        def master_init(kv):
            kv.set_gradient_compression({"type": "bsc", "threshold": 0.5})
            for i, leaf in enumerate(leaves0):
                kv.init(i, leaf)
            kv.wait()

        def worker(kv):
            widx = topo.workers.index(kv)
            tr = DeviceResidentTrainer(list(leaves0), kv, loss_fn,
                                       threshold=0.5, learning_rate=0.05,
                                       momentum=0.9)
            X = jnp.asarray(1.0 + 0.25 * widx)
            for r in range(rounds):
                tr.step(X, None)
                meet.wait(60)
                if widx == 0 and r in (0, rounds - 1):
                    sent.append(messages())
                meet.wait(60)
            results[widx] = ([np.asarray(l).copy() for l in tr.leaves],
                             len(tr._chunks))

        topo.run_workers(worker, include_master=master_init, timeout=300)
    finally:
        topo.stop()
        telemetry.enable(was_on)
    assert len(results) == n
    for w in range(1, n):
        assert results[w][1] == results[0][1]
        for a, b in zip(results[0][0], results[w][0]):
            np.testing.assert_array_equal(a, b)
    return (results[0][0], results[0][1],
            (sent[1] - sent[0]) / (rounds - 1))


@pytest.mark.time_limit(300)
@pytest.mark.parametrize("workers_per_party", [1, 2])
def test_trainer_round_under_a_declared_link_is_the_one_chunk_round(
        workers_per_party):
    """The arithmetic with the party servers' Bi-Sparse ON: a declared
    link whose bandwidth-delay product clamps to the floor of 65,536
    bytes cuts seven keys of some 8k elements (32 KB of selection each
    at threshold 0.5) into four chunks, every chunk its own message on
    both hops and its own pass of the party servers' selection, and the
    leaves are the one-chunk round's bit for bit: a key's boundary
    sample comes out of the server's one generator in the order keys
    complete, which chunks in layer order on FIFO links keep."""
    plan = json.dumps({"default": {"tier": "global", "rtt_ms": 1.0,
                                   "bw_mbps": 100.0}})
    one, nchunks, msgs_one = _run_bsc_trainer({}, workers_per_party)
    assert nchunks == 1
    cut, nchunks, msgs_cut = _run_bsc_trainer({"shape_plan": plan},
                                              workers_per_party)
    assert nchunks == 4
    # a chunk: a push and its answer a worker, a forward and its answer
    # a party
    a_chunk = 2 * 2 * workers_per_party + 2 * 2
    assert msgs_one == a_chunk and msgs_cut == a_chunk * nchunks
    for a, b in zip(one, cut):
        np.testing.assert_array_equal(a, b)
    assert all(np.abs(a - l0).sum() > 0
               for a, l0 in zip(cut, _bsc_leaves()))


# ---------------------------------------------------------------------------
# host-trainer overlap (deferred barrier)
# ---------------------------------------------------------------------------

def test_trainer_overlap_defers_barrier_same_results():
    """Trainer(overlap=True) returns from step() with the round in
    flight; the next leaves access joins it. Weights after N steps
    must equal the blocking trainer's exactly."""
    from geomx_tpu.trainer import Trainer

    def run(overlap):
        topo = InProcessHiPS(num_parties=2,
                             workers_per_party=1).start()
        result = {}
        try:
            def master_init(kv):
                kv.set_optimizer(SGD(learning_rate=0.5))
                kv.init(0, np.ones(6, np.float32))
                kv.wait()

            def worker(kv):
                widx = 0 if kv is topo.workers[0] else 1
                tr = Trainer([np.ones(6, np.float32)], kv,
                             overlap=overlap)
                rng = np.random.RandomState(23)
                for _ in range(4):
                    g = rng.uniform(-1, 1, 6).astype(np.float32)
                    tr.step([g])
                    # leaves joins the in-flight round before reading
                    assert tr.leaves[0].shape == (6,)
                result[widx] = tr.leaves[0].copy()

            topo.run_workers(worker, include_master=master_init,
                             timeout=300)
        finally:
            topo.stop()
        np.testing.assert_array_equal(result[0], result[1])
        return result[0]

    blocking = run(False)
    overlapped = run(True)
    np.testing.assert_array_equal(blocking, overlapped)
