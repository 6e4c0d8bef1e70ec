"""Resender (ACK/retransmit) tests under deterministic fault injection.

Mirrors the reference pairing of ``PS_DROP_MSG`` random message drops
(van.cc:498-499, 871-877) with the ACK resender (resender.h:15-141): a
lossy transport with resend enabled must still complete every push/pull,
and retransmits must not double-apply server-side aggregation.

Loss is injected through the declarative FaultPlan layer (a seeded
``drop`` rule) rather than the legacy uniform ``drop_rate``, so every
run sees the same drop schedule and failures reproduce byte-for-byte.
"""

import json
import threading

import numpy as np
import pytest

from geomx_tpu.config import Config
from geomx_tpu.ps.kv_app import KVPairs, KVServer, KVWorker
from tests.harness import make_tier, shutdown


def make_lossy_tier(drop_rate, num_workers=2, num_servers=1,
                    resend_timeout_ms=100, seed=1234):
    kw_cfg = dict(resend=True, resend_timeout_ms=resend_timeout_ms,
                  ps_seed=seed)
    if drop_rate:
        # seeded drop rule: same schedule on every run (control frames
        # are exempt by default, so rendezvous always completes)
        kw_cfg["fault_plan"] = json.dumps(
            {"rules": [{"type": "drop", "p": drop_rate}]})
    return make_tier(num_workers, num_servers, cfg=Config(**kw_cfg))


def test_sig_assignment_and_ack_clears_pending():
    sched, servers, workers = make_lossy_tier(drop_rate=0.0)
    try:
        store = {}
        server = KVServer(servers[0])

        def handle(req, kvs, srv):
            if req.push:
                for k, v in zip(kvs.keys, kvs.vals):
                    store[k] = store.get(k, 0) + v
                srv.response(req)

        server.set_request_handle(handle)
        w = KVWorker(workers[0])
        ts = w.push(KVPairs(keys=[1], vals=[np.ones(4, np.float32)]),
                    server_rank=0)
        w.wait(ts, 10)
        # all ACKs should drain the outgoing tables on both sides
        for po in [*workers, *servers]:
            r = po.van._resender
            assert r is not None
            for _ in range(100):
                if r.pending() == 0:
                    break
                threading.Event().wait(0.05)
            assert r.pending() == 0
    finally:
        shutdown(sched, *servers, *workers)


def test_lossy_push_pull_completes_exactly_once():
    """30% data-frame loss: pushes still aggregate exactly once each."""
    sched, servers, workers = make_lossy_tier(drop_rate=0.3)
    try:
        store = {}
        applied = []
        lock = threading.Lock()
        server = KVServer(servers[0])

        def handle(req, kvs, srv):
            if req.push:
                with lock:
                    applied.append(req.sender)
                    for k, v in zip(kvs.keys, kvs.vals):
                        store[k] = store.get(k, 0) + v
                srv.response(req)
            elif req.pull:
                srv.response(req, KVPairs(
                    keys=kvs.keys, vals=[store[k] for k in kvs.keys]))

        server.set_request_handle(handle)
        w0, w1 = KVWorker(workers[0]), KVWorker(workers[1])
        v = np.ones((8,), dtype=np.float32)
        n_rounds = 5
        for _ in range(n_rounds):
            ts0 = w0.push(KVPairs(keys=[7], vals=[v]), server_rank=0)
            ts1 = w1.push(KVPairs(keys=[7], vals=[v]), server_rank=0)
            w0.wait(ts0, 60)
            w1.wait(ts1, 60)
        ts = w0.pull([7], server_rank=0)
        w0.wait(ts, 60)
        (resp,) = w0.take_response(ts)
        # exactly 2 workers x n_rounds pushes applied, despite drops+resends
        assert len(applied) == 2 * n_rounds
        np.testing.assert_allclose(resp.vals[0], 2 * n_rounds * v)
        total_resends = sum(po.van._resender.num_resends
                            for po in [*workers, *servers])
        assert total_resends > 0, "drop_rate=0.3 but nothing was resent?"
    finally:
        shutdown(sched, *servers, *workers)


def test_duplicate_suppression():
    """Exact duplicate frames (same signature — i.e. a retransmit whose
    original actually arrived) must be suppressed: server-side effects
    stay exactly-once."""
    sched, servers, workers = make_lossy_tier(drop_rate=0.0)
    try:
        count = [0]
        server = KVServer(servers[0])

        def handle(req, kvs, srv):
            if req.push:
                count[0] += 1
                srv.response(req)

        server.set_request_handle(handle)
        # transport-level duplicate injection: every data frame is sent
        # twice with the same already-assigned signature, exactly what a
        # retransmit after a lost ACK looks like on the wire
        van = workers[0].van
        orig = van._send_one_inner

        def dup_send(target, msg):
            n = orig(target, msg)
            if not msg.is_control:
                orig(target, msg)
            return n

        van._send_one_inner = dup_send
        w = KVWorker(workers[0])
        ts = w.push(KVPairs(keys=[3], vals=[np.ones(4, np.float32)]),
                    server_rank=0)
        w.wait(ts, 30)
        threading.Event().wait(0.3)  # let the duplicate arrive and settle
        assert count[0] == 1
        dups = servers[0].van._resender.num_duplicates
        assert dups >= 1, "expected at least one suppressed duplicate"
    finally:
        shutdown(sched, *servers, *workers)


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
