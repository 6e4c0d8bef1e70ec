"""TSEngine tests: scheduler matchmaking unit tests + overlay integration.

Covers the reference behaviors of ProcessAskPush/PullCommand (reference:
3rdparty/ps-lite/src/van.cc:1197-1458), the worker merge relay
(WorkersMerge, src/kvstore/kvstore_dist.h:91-121) and AutoPull model
dissemination (include/ps/kv_app.h:549-659,1694) — re-implemented in
geomx_tpu/ps/tsengine.py.
"""

import json
import types

import numpy as np
import pytest

from geomx_tpu.optimizer import SGD
from geomx_tpu.ps import base as psbase
from geomx_tpu.ps.message import Control, Message, Meta
from geomx_tpu.ps.tsengine import DONE_DEST, SERVER_DEST, TSScheduler

from tests.harness import SingleTier, Topology, _parallel


class FakeVan:
    is_global = False

    def __init__(self, dead=()):
        self.sent = []
        self.dead = set(dead)

    def send(self, msg):
        self.sent.append(msg)

    def declared_dead_ids(self):
        return frozenset(self.dead)


def _ask(sched, cmd, sender, **body):
    msg = Message(Meta(control_cmd=cmd,
                       body=json.dumps(body)))
    msg.meta.sender = sender
    sched.handle(msg)


def _replies(van):
    out = []
    for m in van.sent:
        d = json.loads(m.meta.body)
        out.append((m.meta.recver, d))
    van.sent.clear()
    return out


def test_scheduler_push_pairing_reduces_to_server():
    """4 workers ask with nm=1: the scheduler pairs them into a reduction
    tree; the final holder (nm=4) is told to push to the server."""
    van = FakeVan()
    sched = TSScheduler(van, num_workers=4, greed_rate=1.0)
    w = [psbase.worker_rank_to_id(r) for r in range(4)]

    for wid in w:
        _ask(sched, Control.ASKPUSH, wid, key=0, off=0, ver=1, nm=1, tgt=4)
    rep = _replies(van)
    assert len(rep) == 2  # two pairs formed
    senders = {to for to, _ in rep}
    receivers = {d["dest"] for _, d in rep}
    assert senders.isdisjoint(receivers)
    assert all(d["kind"] == "push" for _, d in rep)

    # the two receivers merged -> re-ask with nm=2
    for r in receivers:
        _ask(sched, Control.ASKPUSH, r, key=0, off=0, ver=1, nm=2, tgt=4)
    rep = _replies(van)
    assert len(rep) == 1
    final_recv = rep[0][1]["dest"]

    # final holder has everything -> push to server
    _ask(sched, Control.ASKPUSH, final_recv, key=0, off=0, ver=1, nm=4, tgt=4)
    rep = _replies(van)
    assert rep == [(final_recv, {"kind": "push", "key": 0, "off": 0,
                                 "ver": 1, "dest": SERVER_DEST})]


def test_scheduler_pull_relay_serves_every_worker_once():
    van = FakeVan()
    sched = TSScheduler(van, num_workers=3, greed_rate=0.0)
    server = psbase.server_rank_to_id(0)
    served = set()

    # the server keeps asking; each reply hands out a fresh worker
    for _ in range(3):
        _ask(sched, Control.ASKPULL, server, key=5, off=0, ver=2)
        [(_, d)] = _replies(van)
        assert d["dest"] not in served and d["dest"] != DONE_DEST
        served.add(d["dest"])
    assert len(served) == 3

    _ask(sched, Control.ASKPULL, server, key=5, off=0, ver=2)
    [(_, d)] = _replies(van)
    assert d["dest"] == DONE_DEST


def test_scheduler_pull_excludes_holder():
    """A worker that already holds the model is never chosen to receive."""
    van = FakeVan()
    sched = TSScheduler(van, num_workers=2, greed_rate=1.0)
    holder = psbase.worker_rank_to_id(0)
    _ask(sched, Control.ASKPULL, holder, key=1, off=0, ver=1)
    [(_, d)] = _replies(van)
    assert d["dest"] == psbase.worker_rank_to_id(1)


def test_scheduler_pull_skips_declared_dead():
    """Dissemination never targets a declared-dead worker: the model hop
    would park in the resender against a corpse (GX-P3xx fix)."""
    dead = psbase.worker_rank_to_id(1)
    van = FakeVan(dead={dead})
    sched = TSScheduler(van, num_workers=2, greed_rate=0.0)
    server = psbase.server_rank_to_id(0)
    _ask(sched, Control.ASKPULL, server, key=2, off=0, ver=1)
    [(_, d)] = _replies(van)
    assert d["dest"] == psbase.worker_rank_to_id(0)
    # the only live worker is served: the round is done, not stalled
    _ask(sched, Control.ASKPULL, server, key=2, off=0, ver=1)
    [(_, d)] = _replies(van)
    assert d["dest"] == DONE_DEST


def _make_tsnode(tgt_merge, stale=False):
    from geomx_tpu.ps.tsengine import TSNode

    po = types.SimpleNamespace(
        attach_ts=lambda node: None, is_global=False,
        van=types.SimpleNamespace(is_stale=lambda s, e: stale))
    return TSNode(po, kvw=None, tgt_merge=tgt_merge)


def test_tsnode_tgt_accepts_callable_live_view():
    """tgt re-evaluates a callable target per ask — a static int frozen
    at construction can never be satisfied after a death (GX-P305)."""
    live = [3]
    node = _make_tsnode(lambda: live[0])
    assert node.tgt == 3
    live[0] = 2          # a contributor died; the live view shrank
    assert node.tgt == 2
    live[0] = 0
    assert node.tgt == 1  # floor: a round needs at least one party
    assert _make_tsnode(4).tgt == 4  # plain ints still work


def test_tsnode_drops_stale_relay_without_ack():
    """A zombie peer's DATA_TS_RELAY hop is fence-dropped: no merge into
    the slot countdown and no ack (same fence as _handle_data)."""
    from geomx_tpu.ps.tsengine import DATA_TS_RELAY

    node = _make_tsnode(2, stale=True)
    app = types.SimpleNamespace(responses=[])
    app.response = lambda req, kvs=None, body="": app.responses.append(req)
    req = types.SimpleNamespace(simple_app=False, push=True,
                                head=DATA_TS_RELAY, sender=9, epoch=1,
                                version=1, num_merge=1)
    assert node.handle_request(req, None, app) is True  # consumed
    assert app.responses == []                          # ... silently
    assert node._slots == {}                            # ... untouched


def test_scheduler_push_greedy_prefers_fat_links():
    """Under a heterogeneous throughput matrix the greedy matchmaking
    measurably prefers the fat link: with four askers pending and one
    pair's measured throughput far above the rest, that pair is formed
    (in the reported direction)."""
    van = FakeVan()
    sched = TSScheduler(van, num_workers=4, greed_rate=1.0)
    w = [psbase.worker_rank_to_id(r) for r in range(4)]
    # sender-side reports ride the asks: w0->w1 is the fat metro link,
    # everything else measured thin
    _ask(sched, Control.ASKPUSH, w[0], key=0, off=0, ver=1, nm=1, tgt=4,
         rep=[[w[1], 500.0], [w[2], 2.0], [w[3], 2.0]])
    _ask(sched, Control.ASKPUSH, w[1], key=0, off=0, ver=1, nm=1, tgt=4,
         rep=[[w[0], 3.0], [w[2], 2.0]])
    rep = _replies(van)
    # two askers pending -> one pair; greedy must pick the fat direction
    assert rep == [(w[0], {"kind": "push", "key": 0, "off": 0, "ver": 1,
                           "dest": w[1]})]


def test_scheduler_degraded_link_triggers_reroute():
    """A link whose measured throughput collapses (EWMA decays on every
    fresh report) stops being chosen: the scheduler re-routes the pair
    through the now-fastest link."""
    van = FakeVan()
    sched = TSScheduler(van, num_workers=4, greed_rate=1.0)
    w = [psbase.worker_rank_to_id(r) for r in range(4)]
    sched._update_tput(w[0], w[1], 1000.0)   # initially fat
    sched._update_tput(w[2], w[3], 100.0)    # steady mid link
    assert sched._pick_pair({w[0], w[1], w[2], w[3]}) == (w[0], w[1])
    # the fat link degrades: repeated slow measurements pull the EWMA
    # under the mid link
    for _ in range(8):
        sched._update_tput(w[0], w[1], 1.0)
    assert sched.A[(w[0], w[1])] < sched.A[(w[2], w[3])]
    assert sched._pick_pair({w[0], w[1], w[2], w[3]}) == (w[2], w[3])


def test_scheduler_greedy_prefers_measured_throughput():
    van = FakeVan()
    sched = TSScheduler(van, num_workers=3, greed_rate=1.0)
    server = psbase.server_rank_to_id(0)
    w = [psbase.worker_rank_to_id(r) for r in range(3)]
    # report: server->w2 is the fast link
    _ask(sched, Control.ASKPULL, server, key=9, off=0, ver=1,
         rep=[[w[2], 1000.0], [w[0], 1.0]])
    [(_, d)] = _replies(van)
    assert d["dest"] == w[2]


def test_intra_ts_single_tier_end_to_end():
    """3 workers under ENABLE_INTRA_TS: gradients merge worker-to-worker,
    one merged push hits the server, the model relays back; results match
    the direct-push semantics exactly."""
    with SingleTier(extra={"enable_intra_ts": True}, num_workers=3) as topo:
        kvs = topo.workers
        rank0 = next(kv for kv in kvs if kv.rank == 0)
        rank0.set_optimizer(SGD(learning_rate=0.5))
        w0 = np.arange(12, dtype=np.float32)
        _parallel([lambda kv=kv: kv.init(7, w0) for kv in kvs])

        def step(kv, expect):
            kv.push(7, np.ones(12, np.float32))
            out = kv.pull(7)
            kv.wait()
            np.testing.assert_allclose(out, expect, rtol=1e-6)

        # each round: w -= 0.5 * sum(3 x ones) = w - 1.5
        _parallel([lambda kv=kv: step(kv, w0 - 1.5) for kv in kvs])
        _parallel([lambda kv=kv: step(kv, w0 - 3.0) for kv in kvs])
        _parallel([lambda kv=kv: step(kv, w0 - 4.5) for kv in kvs])


def test_intra_ts_hips_two_tier():
    """Full HiPS topology with intra-DC TSEngine: parity with the vanilla
    FSA result (test_hips_fsa_vanilla)."""
    topo = Topology(extra_cfg=dict(enable_intra_ts=True)).start(
        sync_global=True)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.arange(24, dtype=np.float32)
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in topo.workers + [topo.master]])

        def step(kv, expect):
            kv.push(0, np.ones(24, np.float32))
            out = kv.pull(0)
            kv.wait()
            np.testing.assert_allclose(out, expect)

        _parallel([lambda kv=kv: step(kv, w0 - 4.0) for kv in topo.workers])
        _parallel([lambda kv=kv: step(kv, w0 - 8.0) for kv in topo.workers])
    finally:
        topo.stop()


def test_inter_ts_hips_two_tier():
    """HiPS with inter-DC TSEngine: party aggregates merge party-to-party
    before one merged push reaches the global server; the fresh model
    relays back through the party servers."""
    topo = Topology(extra_cfg=dict(enable_inter_ts=True)).start(
        sync_global=True)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.arange(16, dtype=np.float32)
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in topo.workers + [topo.master]])

        def step(kv, expect):
            kv.push(0, np.ones(16, np.float32))
            out = kv.pull(0)
            kv.wait()
            np.testing.assert_allclose(out, expect)

        _parallel([lambda kv=kv: step(kv, w0 - 4.0) for kv in topo.workers])
        _parallel([lambda kv=kv: step(kv, w0 - 8.0) for kv in topo.workers])
    finally:
        topo.stop()


def test_intra_and_inter_ts_combined():
    topo = Topology(extra_cfg=dict(enable_intra_ts=True,
                                   enable_inter_ts=True)).start(
        sync_global=True)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.zeros(10, np.float32)
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in topo.workers + [topo.master]])

        def step(kv, expect):
            kv.push(0, np.ones(10, np.float32))
            out = kv.pull(0)
            kv.wait()
            np.testing.assert_allclose(out, np.full(10, expect))

        _parallel([lambda kv=kv: step(kv, -4.0) for kv in topo.workers])
        _parallel([lambda kv=kv: step(kv, -8.0) for kv in topo.workers])
    finally:
        topo.stop()


def _shaped_direct_vs_overlay(parties, size, rounds, shape_plan):
    """Run identical integer-gradient training on a SHAPED in-process
    HiPS cluster twice — direct global wire, then the inter-DC TSEngine
    overlay — and return (direct, overlay) final models. Gradients are
    integer-valued, so float32 summation is exact in ANY merge order:
    the two wires must agree bit for bit, not just within tolerance."""
    from geomx_tpu.optimizer import SGD
    from geomx_tpu.simulate import InProcessHiPS

    w0 = np.arange(size, dtype=np.float32)
    finals = {}
    for inter_ts in (False, True):
        topo = InProcessHiPS(
            num_parties=parties, workers_per_party=1,
            extra_cfg=dict(shape_plan=shape_plan,
                           enable_inter_ts=inter_ts)).start()
        outs = []
        try:
            def master_init(kv):
                kv.set_optimizer(SGD(learning_rate=1.0))
                kv.init(0, w0.copy())
                kv.wait()

            def worker(kv):
                out = w0.copy()
                kv.init(0, w0.copy())
                for r in range(rounds):
                    kv.push(0, np.full(size, float(r + 1), np.float32))
                    kv.pull(0, out=out)
                    kv.wait()
                outs.append(out.copy())

            topo.run_workers(worker, include_master=master_init,
                             timeout=600)
        finally:
            topo.stop()
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)
        finals[inter_ts] = outs[0]
    return finals[False], finals[True]


# every global link shaped, with the global server's access pipe as a
# SHARED bucket — the small-delay twin of scripts/shapes/hetero16.json
_SHAPED_4P = json.dumps({
    "seed": 3,
    "links": [
        {"dst": 8, "tier": "global", "shared": True,
         "rtt_ms": 4.0, "bw_mbps": 400.0},
        {"src": 8, "tier": "global", "shared": True,
         "rtt_ms": 4.0, "bw_mbps": 400.0},
    ],
    "default": {"tier": "global", "rtt_ms": 4.0, "bw_mbps": 400.0},
})


def test_shaped_overlay_round_bit_exact_vs_direct():
    """A shaped global round through the TSEngine overlay produces the
    SAME bits as the direct wire (4 parties, shared server access pipe
    + per-pair shaped links)."""
    parties, rounds = 4, 2
    direct, overlay = _shaped_direct_vs_overlay(
        parties, size=64, rounds=rounds, shape_plan=_SHAPED_4P)
    np.testing.assert_array_equal(direct, overlay)
    # and both equal the analytic result: w -= sum_p grad_r each round
    expect = np.arange(64, dtype=np.float32) - sum(
        parties * (r + 1) for r in range(rounds))
    np.testing.assert_array_equal(direct, expect)


@pytest.mark.slow
def test_shaped_hetero16_round_bit_exact_vs_direct():
    """The full 16-party heterogeneous plan (fat metro / mid / thin
    transoceanic links, shared server pipe): overlay == direct wire,
    bit for bit. Slow: two 16-party clusters with 150 ms thin links."""
    import os

    plan = "@" + os.path.join(os.path.dirname(__file__), "..",
                              "scripts", "shapes", "hetero16.json")
    direct, overlay = _shaped_direct_vs_overlay(
        16, size=64, rounds=2, shape_plan=plan)
    np.testing.assert_array_equal(direct, overlay)
    expect = np.arange(64, dtype=np.float32) - sum(
        16 * (r + 1) for r in range(2))
    np.testing.assert_array_equal(direct, expect)


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
