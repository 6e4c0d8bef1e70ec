"""Wire sanitizer (geomx_tpu/ps/sanitizer.py) tests.

Unit half: a StubVan drives WireSanitizer's ledgers directly and proves
each violation class fires (and that the legal patterns — fenced stale
drops, give-ups, clean request/response pairs — stay silent).

Integration half: a real in-process tier runs push/pull rounds under a
seeded drop+dup+reorder FaultPlan with the sanitizer enabled on every
van; the run must complete with zero violations (the ISSUE acceptance
bar: chaos + sanitizer = clean).
"""

import json
import threading
import types

import numpy as np
import pytest

from geomx_tpu.ps.sanitizer import MARKER, WireSanitizer

assert MARKER  # the grep target scripts/run_chaos_matrix.sh fails on


class StubVan:
    def __init__(self, my_id=8, dead=(), stale=()):
        self.my_id = my_id
        self._dead = set(dead)
        # (sender, epoch) pairs considered stale
        self._stale = set(stale)

    def declared_dead_ids(self):
        return frozenset(self._dead)

    def is_stale(self, sender, epoch):
        return (sender, epoch) in self._stale


def msg(*, sender=9, recver=8, ts=1, request=True, push=False, pull=False,
        epoch=0, control=False):
    m = types.SimpleNamespace()
    m.meta = types.SimpleNamespace(
        sender=sender, recver=recver, app_id=0, customer_id=0,
        timestamp=ts, request=request, push=push, pull=pull,
        simple_app=False, head=0, epoch=epoch, msg_type=0)
    m.is_control = control
    return m


def test_clean_request_response_cycle():
    san = WireSanitizer(StubVan())
    san.on_inbound(msg(sender=9, ts=5, request=True, push=True))
    san.on_send(9, msg(recver=9, ts=5, request=False))
    assert san.report() == []


def test_double_response_is_unmatched(caplog):
    san = WireSanitizer(StubVan())
    san.on_inbound(msg(sender=9, ts=5, request=True, push=True))
    san.on_send(9, msg(recver=9, ts=5, request=False))
    with caplog.at_level("ERROR", logger="geomx.sanitizer"):
        san.on_send(9, msg(recver=9, ts=5, request=False))  # double ack
    assert any("unmatched-response" in v for v in san.violations)
    assert MARKER in caplog.text


def test_send_to_declared_dead_node():
    san = WireSanitizer(StubVan(dead={11}))
    san.on_send(11, msg(recver=11, ts=3, request=True, push=True))
    assert any("send-to-dead" in v for v in san.violations)


def test_epoch_regression():
    san = WireSanitizer(StubVan())
    san.on_inbound(msg(sender=9, ts=1, push=True, epoch=2))
    san.on_send(9, msg(recver=9, ts=1, request=False))
    san.on_inbound(msg(sender=9, ts=2, push=True, epoch=1))  # regression
    assert any("epoch-regression" in v for v in san.violations)


def test_duplicate_request_delivery():
    san = WireSanitizer(StubVan())
    san.on_inbound(msg(sender=9, ts=5, push=True))
    san.on_inbound(msg(sender=9, ts=5, push=True))  # past the dedup
    assert any("duplicate-request" in v for v in san.violations)


def test_unacked_request_leaks_at_report():
    san = WireSanitizer(StubVan())
    san.on_inbound(msg(sender=9, ts=5, push=True))
    report = san.report()
    assert any("countdown leak" in v for v in report)
    # idempotent: a second report (van.stop after a manual one) does not
    # double-count
    assert san.report() == report


def test_unanswered_request_leaks_at_report():
    san = WireSanitizer(StubVan())
    san.on_send(8, msg(sender=9, recver=8, ts=7, request=True, pull=True))
    assert any("unanswered-request" in v for v in san.report())


def test_give_up_resolves_outbound_and_forgives_late_reply():
    san = WireSanitizer(StubVan())
    m = msg(sender=9, recver=8, ts=7, request=True, pull=True)
    san.on_send(8, m)
    san.on_give_up(m)
    # the late response arriving after the give-up is not a violation
    san.on_inbound(msg(sender=8, ts=7, request=False))
    assert san.report() == []


def test_shutdown_forgives_inflight_request():
    """van.stop() is the give-up for anything still awaiting a response
    (the final teardown ack can always be lost — two generals): where a
    manual report() flags the unanswered request, on_shutdown forgives
    it, and a response landing even later is still not a double-ack."""
    san = WireSanitizer(StubVan())
    san.on_send(8, msg(sender=9, recver=8, ts=7, request=True, pull=True))
    assert san.on_shutdown() == []
    san.on_inbound(msg(sender=8, ts=7, request=False))
    assert san.violations == []


def test_fenced_stale_push_drop_is_legal():
    """A push the server fence-drops via is_stale owes no ack."""
    san = WireSanitizer(StubVan(stale={(9, 1)}))
    san.on_inbound(msg(sender=9, ts=5, push=True, epoch=1))
    assert san.report() == []


def test_control_frames_are_ignored():
    san = WireSanitizer(StubVan(dead={11}))
    san.on_send(11, msg(recver=11, ts=3, control=True))
    san.on_inbound(msg(sender=9, ts=4, control=True))
    assert san.report() == []


# ---------------------------------------------------------------------------
# integration: chaos round-trip with the sanitizer on every van
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_push_pull_with_sanitizer_clean():
    """Drop + dup + reorder faults, resend on, sanitizer on: training
    traffic completes and EVERY van closes with zero violations."""
    from geomx_tpu.config import Config
    from geomx_tpu.ps.kv_app import KVPairs, KVServer, KVWorker
    from tests.harness import make_tier, shutdown

    cfg = Config(
        resend=True, resend_timeout_ms=20, ps_seed=77,
        wire_sanitizer=True,
        fault_plan=json.dumps({"rules": [
            {"type": "drop", "p": 0.15},
            {"type": "reorder", "window": 4},
            {"type": "dup", "p": 0.1},
        ]}))
    sched, servers, workers = make_tier(cfg=cfg)
    pos = [sched] + servers + workers
    try:
        for po in pos:
            assert po.van.sanitizer is not None
        store = {}
        lock = threading.Lock()
        server = KVServer(servers[0])

        def handle(req, kvs, srv):
            if req.push:
                with lock:
                    for k, v in zip(kvs.keys, kvs.vals):
                        store[k] = store.get(k, 0) + v
                srv.response(req)
            elif req.pull:
                srv.response(req, KVPairs(
                    keys=kvs.keys, vals=[store[k] for k in kvs.keys]))

        server.set_request_handle(handle)
        w0, w1 = KVWorker(workers[0]), KVWorker(workers[1])
        v = np.ones((16,), dtype=np.float32)
        n_rounds = 4
        for _ in range(n_rounds):
            ts0 = w0.push(KVPairs(keys=[7], vals=[v]), server_rank=0)
            ts1 = w1.push(KVPairs(keys=[7], vals=[v]), server_rank=0)
            w0.wait(ts0, 60)
            w1.wait(ts1, 60)
        ts = w0.pull([7], server_rank=0)
        w0.wait(ts, 60)
        (resp,) = w0.take_response(ts)
        np.testing.assert_allclose(resp.vals[0], 2 * n_rounds * v)
    finally:
        shutdown(sched, *servers, *workers)
    for po in pos:
        assert po.van.sanitizer.report() == [], (
            f"van {po.van.my_id}: {po.van.sanitizer.violations}")


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
