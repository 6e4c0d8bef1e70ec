"""The one round of the worker's store (``kvstore.dist._ServerRound``),
held to its contract through the verbs that share it.

The store here has no van: its ``kvw`` is a stub the test drives by
hand (``push`` / ``pull`` record what was sent, ``answer`` plays a
server's response or the transport's give-up on the calling thread), so
every case is exact and none sleeps. Key 0 is one shard on server 0;
key 1 is two shards, one a server, so it finishes only when both
servers answered.
"""

import dataclasses
import threading

import numpy as np
import pytest

from geomx_tpu import config as cfg_mod
from geomx_tpu.compression.device import WireCodec
from geomx_tpu.kvstore import sharding
from geomx_tpu.kvstore.dist import KVStoreDist, _KeyInfo
from geomx_tpu.kvstore.frontier import WorkerLostError
from geomx_tpu.ps.kv_app import KVPairs

N0, N1 = 6, 8
SENTINEL = 7.5


class _Sent:
    def __init__(self, kind, kvs, keys, rank, kw):
        self.kind, self.kvs, self.keys, self.rank = kind, kvs, keys, rank
        self.priority = kw["priority"]
        self.compr = kvs.compr if kvs is not None else kw.get("compr", "")
        self.offsets = list(kvs.offsets if kvs is not None
                            else kw["offsets"])
        self.trace_round = kw.get("trace_round", -1)
        self.cb = kw["cb"]


class _FakeKVW:
    """Stands for ``ps.kv_app.KVWorker``: the four methods the round
    uses, plus ``answer`` for the test to play the other side."""

    def __init__(self):
        self.sent = []
        self._fail = {}
        self._resp = {}

    def push(self, kvs, rank, **kw):
        self.sent.append(_Sent("push", kvs, kvs.keys, rank, kw))
        return len(self.sent) - 1

    def pull(self, keys, rank, **kw):
        self.sent.append(_Sent("pull", None, keys, rank, kw))
        return len(self.sent) - 1

    def take_failure(self, ts):
        return self._fail.pop(ts, None)

    def take_response(self, ts):
        return self._resp.pop(ts, [])

    def answer(self, ts, kvs=None, fail=None):
        if fail is not None:
            self._fail[ts] = fail
        elif kvs is not None:
            self._resp[ts] = [kvs]
        self.sent[ts].cb(ts)


class _Van:
    def round_args(self, rid):
        return {"node": "w0", "tier": "local", "round": rid}


class _Po:
    van = _Van()
    num_servers = 2


def _store(chunk_retries=0):
    kv = KVStoreDist.__new__(KVStoreDist)
    kv.cfg = dataclasses.replace(cfg_mod.Config(),
                                 chunk_retries=chunk_retries)
    kv.po, kv.kvw = _Po(), _FakeKVW()
    kv._ts, kv._ts_ver, kv._controller = None, {}, None
    kv._lock = threading.Lock()
    kv._cv = threading.Condition(kv._lock)
    kv._push_acks_left, kv._deferred = {}, {}
    kv._outstanding, kv._outstanding_key = 0, {}
    kv._transport_errors, kv._round_seq = [], 0
    kv._wire = WireCodec.from_config(kv.cfg)
    kv._key_info = {
        0: _KeyInfo(N0, (N0,), np.dtype(np.float32),
                    [sharding.Shard(0, 0, N0, N0)]),
        1: _KeyInfo(N1, (N1,), np.dtype(np.float32),
                    [sharding.Shard(0, 0, 4, N1),
                     sharding.Shard(1, 4, 4, N1)]),
    }
    return kv


# what each key's aggregate is when a server does answer: dense ranges,
# and the same as (values, positions) of the nonzeros
DENSE = {0: np.arange(1, N0 + 1, dtype=np.float32),
         1: np.arange(11, N1 + 11, dtype=np.float32)}


def _response(sent, skip=()):
    """A server's answer to ``sent``: an entry a request entry (but
    the keys of ``skip``), in the form the request asks for."""
    sparse = sent.compr in ("bsc", "bsc16")
    out = KVPairs(compr="bsc" if sparse else "")
    for k, off in zip(sent.keys, sent.offsets):
        if k in skip:
            continue
        total = N0 if k == 0 else N1
        length = total if k == 0 else 4
        part = DENSE[k][off:off + length]
        out.keys.append(k)
        out.vals.append(part.copy())
        if sparse:
            out.aux.append(np.arange(length, dtype=np.int32))
        out.offsets.append(off)
        out.totals.append(total)
        out.lens.append(length)
    return out


class _Verb:
    """One verb of the store over keys 0 and 1: how it is called, where
    its result lands and where its give-up surfaces."""

    def __init__(self, name, handed_out, pushes):
        self.name, self.handed_out, self.pushes = name, handed_out, pushes

    def call(self, kv, priority=0, keys=(0, 1)):
        keys = list(keys)
        self.outs = {k: np.full(N0 if k == 0 else N1, SENTINEL, np.float32)
                     for k in keys}
        grads = [np.ones(N0 if k == 0 else N1, np.float32) for k in keys]
        outs = [self.outs[k] for k in keys]
        if self.name == "push_pull":
            return kv.push_pull(keys, grads, outs, priority=priority)
        if self.name == "push_pull_async":
            return kv.push_pull_async(keys, grads, outs, priority=priority)
        if self.name == "pull":
            return kv.pull(keys, out=outs, priority=priority)
        return kv.push_pull_bsc_batch_async(
            keys, [np.ones(2, np.float32) for _ in keys],
            [np.array([0, 5], np.int32) for _ in keys], priority=priority)

    def untouched(self, key, fut):
        """Nothing was written for ``key``: no zeros over the caller's
        array, no empty aggregate handed to the caller."""
        if self.name == "push_pull_bsc_batch_async":
            return not fut.done([key])
        return bool((self.outs[key] == SENTINEL).all())

    def holds(self, key, fut):
        if self.name == "push_pull_bsc_batch_async":
            vals, idx = fut.result(key, timeout=0)
            dense = np.zeros(N0 if key == 0 else N1, np.float32)
            dense[np.asarray(idx)] = vals
            return bool((dense == DENSE[key]).all())
        return bool((self.outs[key] == DENSE[key]).all())

    def join(self, kv, fut):
        """Where the verb's caller joins: the future it was handed, or
        the store's ``wait()``."""
        if self.handed_out:
            fut.wait(timeout=0)
        else:
            kv.wait(timeout=0)


VERBS = [_Verb("push_pull", False, True),
         _Verb("push_pull_async", True, True),
         _Verb("push_pull_bsc_batch_async", True, True),
         _Verb("pull", False, False)]
verbs = pytest.mark.parametrize("verb", VERBS, ids=lambda v: v.name)


def _by_server(kv):
    return {s.rank: ts for ts, s in enumerate(kv.kvw.sent)}


@verbs
def test_a_short_answer_is_pulled_again_and_never_written(verb):
    kv = _store()
    fut = verb.call(kv, priority=5)
    first = list(kv.kvw.sent)
    assert sorted(s.rank for s in first) == [0, 1]
    assert all(s.priority == 5 for s in first)
    at = _by_server(kv)
    # server 0 acks without key 1's entry; server 1 answers in full
    kv.kvw.answer(at[0], _response(first[at[0]], skip=(1,)))
    assert verb.holds(0, fut)
    kv.kvw.answer(at[1], _response(first[at[1]]))
    assert verb.untouched(1, fut), \
        "a key with a part missing was finished from what it had"
    again = kv.kvw.sent[len(first):]
    # the re-pull: a pull of EVERY shard of key 1, nothing of key 0, at
    # the caller's priority and under the round's id
    assert [s.kind for s in again] == ["pull", "pull"]
    assert sorted(s.rank for s in again) == [0, 1]
    assert all(list(s.keys) == [1] and s.priority == 5 for s in again)
    assert {s.trace_round for s in again} \
        == {s.trace_round for s in first}
    if verb.name == "push_pull_bsc_batch_async":
        assert {s.compr for s in again} == {"bsc"}
    # wait() still has the round outstanding while the re-pull is out
    with pytest.raises(TimeoutError):
        kv.wait(timeout=0)
    for ts in range(len(first), len(kv.kvw.sent)):
        kv.kvw.answer(ts, _response(kv.kvw.sent[ts]))
    assert verb.holds(1, fut)
    verb.join(kv, fut)
    kv.wait(timeout=0)


@verbs
def test_a_second_short_answer_is_an_error_not_a_loop(verb):
    kv = _store()
    fut = verb.call(kv)
    n = len(kv.kvw.sent)
    for ts in range(n):
        kv.kvw.answer(ts, _response(kv.kvw.sent[ts], skip=(1,)))
    assert len(kv.kvw.sent) == n + 2
    for ts in range(n, n + 2):
        kv.kvw.answer(ts, _response(kv.kvw.sent[ts], skip=(1,)))
    assert len(kv.kvw.sent) == n + 2, "the re-pull was pulled again"
    assert verb.untouched(1, fut) or verb.handed_out
    with pytest.raises(RuntimeError, match="answered without data"):
        verb.join(kv, fut)
    kv.wait(timeout=0)      # surfaced once


@verbs
@pytest.mark.parametrize("reason, exc", [
    ("node 9 declared dead", WorkerLostError),
    ("delivery deadline exceeded", TimeoutError),
    ("retry cap reached", RuntimeError)])
def test_a_give_up_surfaces_once_where_the_caller_joins(verb, reason, exc):
    kv = _store()
    fut = verb.call(kv)
    at = _by_server(kv)
    kv.kvw.answer(at[1], fail=reason)
    kv.kvw.answer(at[0], _response(kv.kvw.sent[at[0]]))
    assert len(kv.kvw.sent) == 2, "a failed key is not pulled again"
    assert verb.holds(0, fut)
    assert verb.untouched(1, fut) or verb.handed_out
    with pytest.raises(exc, match="key 1"):
        verb.join(kv, fut)
    # exactly once: neither the other join nor a second one raises
    kv.wait(timeout=0)
    if verb.handed_out:
        assert fut.errors(1) and not fut.errors(0)


@verbs
def test_a_failed_chunk_is_resent_as_it_is_while_the_budget_lasts(verb):
    kv = _store(chunk_retries=2)
    fut = verb.call(kv)
    at = _by_server(kv)
    first = kv.kvw.sent[at[1]]
    kv.kvw.answer(at[1], fail="retry cap reached")
    kv.kvw.answer(len(kv.kvw.sent) - 1, fail="retry cap reached")
    resent = kv.kvw.sent[2:]
    assert len(resent) == 2
    for s in resent:
        # the IDENTICAL message: the same KVPairs (encoded once, so a
        # 2-bit residual drains once), the same server and priority
        assert s.kind == first.kind and s.rank == 1
        assert s.kvs is first.kvs and s.keys is first.keys
        assert s.priority == first.priority
    with pytest.raises(TimeoutError):
        kv.wait(timeout=0)          # still registered while it retries
    kv.kvw.answer(len(kv.kvw.sent) - 1, fail="retry cap reached")
    assert len(kv.kvw.sent) == 4, "sent past the budget"
    kv.kvw.answer(at[0], _response(kv.kvw.sent[at[0]]))
    with pytest.raises(RuntimeError, match="key 1"):
        verb.join(kv, fut)


@verbs
def test_a_chunk_is_never_resent_to_a_dead_peer(verb):
    kv = _store(chunk_retries=2)
    fut = verb.call(kv)
    at = _by_server(kv)
    kv.kvw.answer(at[1], fail="node 9 declared dead")
    assert len(kv.kvw.sent) == 2
    kv.kvw.answer(at[0], _response(kv.kvw.sent[at[0]]))
    with pytest.raises(WorkerLostError):
        verb.join(kv, fut)


@pytest.mark.parametrize(
    "verb", [v for v in VERBS if v.pushes]
    + [_Verb("push", False, True)], ids=lambda v: v.name)
def test_a_pull_waits_for_the_rounds_last_ack(verb):
    kv = _store()
    if verb.name == "push":
        kv.push([0, 1], [np.ones(N0, np.float32), np.ones(N1, np.float32)])
        fut = None
    else:
        fut = verb.call(kv)
    at = _by_server(kv)
    pulled = np.full(N1, SENTINEL, np.float32)
    kv.pull(1, out=pulled)
    assert len(kv.kvw.sent) == 2, "the pull left before the round's acks"
    # a plain push's ack carries no data
    kv.kvw.answer(at[0], None if verb.name == "push"
                  else _response(kv.kvw.sent[at[0]]))
    assert len(kv.kvw.sent) == 2, "key 1 still has an ack out"
    kv.kvw.answer(at[1], None if verb.name == "push"
                  else _response(kv.kvw.sent[at[1]]))
    after = kv.kvw.sent[2:]
    assert [s.kind for s in after] == ["pull", "pull"]
    assert all(list(s.keys) == [1] for s in after)
    for ts in (2, 3):
        kv.kvw.answer(ts, _response(kv.kvw.sent[ts]))
    assert (pulled == DENSE[1]).all()
    kv.wait(timeout=0)


@pytest.mark.parametrize("verb", VERBS + [_Verb("push", False, True)],
                         ids=lambda v: v.name)
def test_duplicate_keys_are_refused_before_anything_is_sent(verb):
    kv = _store()
    with pytest.raises((ValueError, AssertionError), match="duplicate"):
        if verb.name == "push":
            kv.push([1, 1], [np.ones(N1, np.float32)] * 2)
        else:
            verb.call(kv, keys=(1, 1))
    assert kv.kvw.sent == [] and kv._outstanding == 0


@pytest.mark.parametrize("form", ["pull", "pull_row_sparse"])
def test_a_blocking_pull_returns_with_its_key_and_leaves_errors_to_wait(
        form):
    """The two blocking forms sit on the same round: they return when
    their key completes, and what went wrong surfaces from ``wait()``."""
    kv = _store()
    kv._key_info[2] = _KeyInfo(N0, (3, 2), np.dtype(np.float32),
                               [sharding.Shard(0, 0, N0, N0)])
    rows = np.arange(1, 5, dtype=np.float32).reshape(2, 2)
    plays = []      # what the server does with each request, in turn

    def pull(keys, rank, **kw):
        ts = _FakeKVW.pull(kv.kvw, keys, rank, **kw)
        kv.kvw.answer(ts, **plays.pop(0)(kv.kvw.sent[ts]))
        return ts

    kv.kvw.pull = pull
    if form == "pull":
        call = lambda: kv.pull(0)
        full = lambda sent: {"kvs": _response(sent)}
        want = DENSE[0]
        plays += [full, lambda sent: {"kvs": KVPairs()},   # short, then
                  lambda sent: {"kvs": KVPairs()}]         # short again
    else:
        call = lambda: kv.pull_row_sparse(2, [0, 2], timeout=0)
        full = lambda sent: {"kvs": KVPairs(
            keys=[2], vals=[rows.ravel()], aux=[np.array([0, 2])],
            offsets=[0], totals=[N0], lens=[2], compr="rsp")}
        want = rows
        # a row-sparse request is not a range: not asked again
        plays += [full, lambda sent: {"kvs": KVPairs(compr="rsp")}]
    assert (call() == want).all()
    kv.wait(timeout=0)
    assert not call().any(), "a short answer came back as data"
    assert not plays, "a request too few or too many"
    with pytest.raises(RuntimeError, match="answered without data"):
        kv.wait(timeout=0)
    kv.wait(timeout=0)      # surfaced once
