"""Stress and regression tests for the HiPS synchronization core.

The round-2 flake (a worker pull returning stale or gradient data) was a
cross-round confusion in the party server's forward/pull-back state
machine: the init-time global pull-back — buffered at the global server
until the master's init — could arrive AFTER the party's workers had
already pushed a full training round, complete the wrong round, and ack
the training pushes early. These tests pin the fix (per-cycle tokens +
outbound staging + pull buffering, geomx_tpu/kvstore/server.py) under
deterministic reorderings, many rounds, CPU load, and message loss.
"""

import threading
import time

import numpy as np
import pytest

from tests.harness import Topology, _parallel
from geomx_tpu.optimizer import SGD


def test_init_training_race_master_delayed():
    """Deterministic reproduction of the round-2 flake's root cause: the
    master's init is delayed so every party's init pull-back is buffered
    at the global server while party workers race ahead into training.
    Before the cycle-token fix this failed nearly always (workers pulled
    w0 instead of w0 - 4)."""
    topo = Topology().start(sync_global=True)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.arange(64, dtype=np.float32)

        def worker_path(kv):
            kv.init(0, w0)
            # no cross-party synchronization: train immediately
            for r in range(1, 4):
                kv.push(0, np.ones(64, np.float32))
                out = np.zeros(64, np.float32)
                kv.pull(0, out=out)
                kv.wait()
                np.testing.assert_allclose(out, w0 - 4.0 * r)

        def master_path(kv):
            time.sleep(0.5)   # widen the init/training race window
            kv.init(0, w0)

        _parallel([lambda kv=kv: worker_path(kv) for kv in topo.workers]
                  + [lambda: master_path(topo.master)])
    finally:
        topo.stop()


def _stress_rounds(topo, keys, w0, rounds, n_workers):
    topo.master.set_optimizer(SGD(learning_rate=1.0))

    def init_on(kv):
        for k in keys:
            kv.init(k, w0[k])

    _parallel([lambda kv=kv: init_on(kv)
               for kv in topo.workers + [topo.master]])

    def train(kv):
        for r in range(1, rounds + 1):
            for k in keys:
                kv.push(k, np.ones_like(w0[k]))
            outs = {k: np.zeros_like(w0[k]) for k in keys}
            for k in keys:
                kv.pull(k, out=outs[k])
            kv.wait()
            for k in keys:
                np.testing.assert_allclose(
                    outs[k], w0[k] - n_workers * r,
                    err_msg=f"key {k} round {r}")

    _parallel([lambda kv=kv: train(kv) for kv in topo.workers])


def test_stress_many_rounds_multi_server_parties_under_load():
    """20 rounds x 3 keys x 2-server parties with background CPU load —
    the configuration and duration under which the round-1/2 freshness
    race reproduced. Values must be exact every round."""
    stop = threading.Event()

    def burn():
        x = np.random.rand(256, 256).astype(np.float32)
        while not stop.is_set():
            x = np.tanh(x @ x.T * 1e-3)

    burners = [threading.Thread(target=burn, daemon=True) for _ in range(4)]
    for b in burners:
        b.start()
    topo = Topology(servers_per_party=2, bigarray_bound=16).start(
        sync_global=True)
    try:
        keys = [0, 1, 2]
        w0 = {0: np.arange(40, dtype=np.float32),
              1: np.ones(8, np.float32) * 3,
              2: np.linspace(-5, 5, 33).astype(np.float32)}
        _stress_rounds(topo, keys, w0, rounds=20, n_workers=4)
    finally:
        stop.set()
        topo.stop()


def test_stress_under_drop_and_resend():
    """Message loss (PS_DROP_MSG) with the retransmit layer (PS_RESEND)
    enabled on every van: rounds must still complete with exact values —
    retransmit-induced duplicates must not double-count pushes or
    barriers (the receipt-time dedup in van._process)."""
    topo = Topology(extra_cfg={"drop_rate": 0.05, "resend": True,
                               "resend_timeout_ms": 200}).start(
        sync_global=True)
    try:
        keys = [0, 1]
        w0 = {0: np.arange(24, dtype=np.float32),
              1: np.full(10, 2.0, np.float32)}
        _stress_rounds(topo, keys, w0, rounds=8, n_workers=4)
    finally:
        topo.stop()


def test_wait_keys_per_key_semantics():
    """wait(keys=[k]) drains only k's outstanding ops (round-2 Weak #8:
    the argument was silently ignored)."""
    topo = Topology().start(sync_global=True)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.zeros(8, np.float32)
        _parallel([lambda kv=kv: (kv.init(0, w0), kv.init(1, w0))
                   for kv in topo.workers + [topo.master]])

        def train(kv):
            kv.push(0, np.ones(8, np.float32))
            kv.push(1, np.ones(8, np.float32))
            out0 = np.zeros(8, np.float32)
            out1 = np.zeros(8, np.float32)
            kv.pull(0, out=out0)
            kv.pull(1, out=out1)
            kv.wait(keys=0)
            np.testing.assert_allclose(out0, w0 - 4.0)
            kv.wait(keys=[1])
            np.testing.assert_allclose(out1, w0 - 4.0)
            kv.wait()

        _parallel([lambda kv=kv: train(kv) for kv in topo.workers])
    finally:
        topo.stop()


def test_optimizer_states_fetched_from_global_tier(tmp_path):
    """A PARTY worker's save_optimizer_states must return the LIVE
    (global-tier) updater states, not the party server's never-updated
    copy (round-2 advisor finding a)."""
    from geomx_tpu import checkpoint as ck
    from geomx_tpu.optimizer import Adam
    import json

    topo = Topology().start(sync_global=True)
    fname = str(tmp_path / "party.states")
    try:
        topo.master.set_optimizer(Adam(learning_rate=0.01))
        w0 = np.ones(16, np.float32)
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in topo.workers + [topo.master]])

        def push_pull(kv):
            kv.push(0, np.ones(16, np.float32))
            kv.pull(0)
            kv.wait()

        for _ in range(3):
            _parallel([lambda kv=kv: push_pull(kv) for kv in topo.workers])

        # save from a party worker (NOT the master): its local servers
        # must relay the GET to the global tier
        party_worker = topo.workers[0]
        assert not party_worker.is_master_worker
        party_worker.save_optimizer_states(fname)
        with open(fname) as f:
            per_server = json.load(f)
        assert per_server, "no states returned"
        states = ck.deserialize_states(
            bytes.fromhex(next(iter(per_server.values()))))
        assert states[(0, 0)]["t"] == 3, \
            "party worker fetched stale (non-global) optimizer states"
        assert np.abs(states[(0, 0)]["m"]).max() > 0

        # round-trip: restore through the party worker too
        party_worker.load_optimizer_states(fname)
        # one more round applies on top of the restored states
        _parallel([lambda kv=kv: push_pull(kv) for kv in topo.workers])
        topo.master.save_optimizer_states(fname)
        with open(fname) as f:
            per2 = json.load(f)
        states2 = ck.deserialize_states(
            bytes.fromhex(next(iter(per2.values()))))
        assert states2[(0, 0)]["t"] == 4
    finally:
        topo.stop()


def test_checkpoint_five_digit_epoch(tmp_path):
    """latest_checkpoint must find epochs >= 10000 ({:04d} renders them
    5 digits wide; round-2 advisor finding d)."""
    from geomx_tpu import checkpoint

    prefix = str(tmp_path / "big")
    for e in (3, 9999, 10001):
        checkpoint.save_checkpoint(prefix, e, [np.zeros(2, np.float32)])
    assert checkpoint.latest_checkpoint(prefix) == 10001


def test_resend_give_up_surfaces_error():
    """When the resender exhausts its retries, the requester's wait()
    must raise promptly instead of blocking to its own timeout (round-2
    advisor finding c). The server drops 100% of inbound DATA frames
    before they reach the resender's dedup/ACK layer, so the worker's
    push is never acknowledged."""
    from geomx_tpu.config import Config
    from geomx_tpu.ps.kv_app import KVPairs, KVWorker
    from geomx_tpu.ps.message import Role
    from geomx_tpu.ps.postoffice import Postoffice
    from geomx_tpu.simulate import free_port

    port = free_port()
    cfg = Config(resend=True, resend_timeout_ms=20)
    blackhole = Config(resend=True, resend_timeout_ms=20, drop_rate=1.0)
    vans = []

    def sched():
        po = Postoffice(my_role=Role.SCHEDULER, is_global=False,
                        root_uri="127.0.0.1", root_port=port,
                        num_workers=1, num_servers=1, cfg=cfg)
        po.start(30)
        vans.append(po.van)

    def server():
        po = Postoffice(my_role=Role.SERVER, is_global=False,
                        root_uri="127.0.0.1", root_port=port,
                        num_workers=1, num_servers=1, cfg=blackhole)
        po.start(30)
        vans.append(po.van)

    for fn in (sched, server):
        threading.Thread(target=fn, daemon=True).start()

    wpo = Postoffice(my_role=Role.WORKER, is_global=False,
                     root_uri="127.0.0.1", root_port=port,
                     num_workers=1, num_servers=1, cfg=cfg)
    wpo.start(30)
    kvw = KVWorker(wpo)
    # cap retries low so the test is fast
    wpo.van._resender.max_retries = 3

    ts = kvw.push(KVPairs(keys=[0], vals=[np.ones(4, np.float32)],
                          offsets=[0], totals=[4], lens=[4]), 0)
    t0 = time.monotonic()
    with pytest.raises((RuntimeError, TimeoutError)) as ei:
        kvw.wait(ts, timeout=30.0)
    elapsed = time.monotonic() - t0
    assert elapsed < 20.0, "give-up did not surface promptly"
    assert isinstance(ei.value, RuntimeError), \
        f"expected fast RuntimeError from give-up, got {ei.value!r}"
    assert "undeliverable" in str(ei.value)
    wpo.finalize(do_barrier=False)       # the customer's thread and the van
    for v in vans:
        v.stop()


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))


def test_non_uniform_party_sizes_exact_counting():
    """Parties running DIFFERENT numbers of local servers: with
    DMLC_NUM_PARTY set (simulate sets it automatically for non-uniform
    topologies) the global server counts rounds exactly — the reference's
    aligned-key counting cannot express this topology at all."""
    topo = Topology(servers_per_party=[2, 1], bigarray_bound=16).start(
        sync_global=True)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        keys = [0, 1]
        w0 = {0: np.arange(40, dtype=np.float32),
              1: np.full(8, 3.0, np.float32)}
        _parallel([lambda kv=kv: [kv.init(k, w0[k]) for k in keys]
                   for kv in topo.workers + [topo.master]])

        def train(kv):
            for r in range(1, 4):
                for k in keys:
                    kv.push(k, np.ones_like(w0[k]))
                outs = {k: np.zeros_like(w0[k]) for k in keys}
                for k in keys:
                    kv.pull(k, out=outs[k])
                kv.wait()
                for k in keys:
                    np.testing.assert_allclose(
                        outs[k], w0[k] - 4.0 * r,
                        err_msg=f"key {k} round {r}")

        _parallel([lambda kv=kv: train(kv) for kv in topo.workers])
    finally:
        topo.stop()


def _stress_rounds_batched(topo, keys, w0, rounds, n_workers):
    """_stress_rounds through the BATCHED list wire (one message per
    server per direction; countdown-merged server responses)."""
    topo.master.set_optimizer(SGD(learning_rate=1.0))

    def init_on(kv):
        for k in keys:
            kv.init(k, w0[k])

    _parallel([lambda kv=kv: init_on(kv)
               for kv in topo.workers + [topo.master]])

    def train(kv):
        for r in range(1, rounds + 1):
            kv.push(keys, [np.ones_like(w0[k]) for k in keys])
            outs = [np.zeros_like(w0[k]) for k in keys]
            kv.pull(keys, out=outs)
            kv.wait()
            for k, out in zip(keys, outs):
                np.testing.assert_allclose(
                    out, w0[k] - n_workers * r,
                    err_msg=f"key {k} round {r}")

    _parallel([lambda kv=kv: train(kv) for kv in topo.workers])


def test_stress_batched_wire_multi_server_parties():
    """The batched multi-key wire under the freshness-race stress
    configuration (2-server parties, sharded keys, many rounds):
    values must be exact every round."""
    topo = Topology(servers_per_party=2, bigarray_bound=16).start(
        sync_global=True)
    try:
        keys = [0, 1, 2]
        w0 = {0: np.arange(40, dtype=np.float32),
              1: np.ones(8, np.float32) * 3,
              2: np.linspace(-5, 5, 33).astype(np.float32)}
        _stress_rounds_batched(topo, keys, w0, rounds=20, n_workers=4)
    finally:
        topo.stop()


def test_stress_batched_wire_under_drop_and_resend():
    """Batched rounds under message loss + retransmit: a dropped or
    duplicated multi-key message must neither double-count any key's
    contribution nor leave the countdown responder short."""
    topo = Topology(extra_cfg={"drop_rate": 0.05, "resend": True,
                               "resend_timeout_ms": 200}).start(
        sync_global=True)
    try:
        keys = [0, 1]
        w0 = {0: np.arange(24, dtype=np.float32),
              1: np.full(10, 2.0, np.float32)}
        _stress_rounds_batched(topo, keys, w0, rounds=8, n_workers=4)
    finally:
        topo.stop()


def _stress_rounds_push_pull(topo, keys, w0, rounds, n_workers):
    """_stress_rounds through the COMBINED push_pull wire (one message
    per server per round; the countdown-merged ack carries the
    post-round params)."""
    topo.master.set_optimizer(SGD(learning_rate=1.0))

    def init_on(kv):
        for k in keys:
            kv.init(k, w0[k])

    _parallel([lambda kv=kv: init_on(kv)
               for kv in topo.workers + [topo.master]])

    def train(kv):
        for r in range(1, rounds + 1):
            outs = [np.zeros_like(w0[k]) for k in keys]
            kv.push_pull(keys, [np.ones_like(w0[k]) for k in keys],
                         out=outs)
            kv.wait()
            for k, out in zip(keys, outs):
                np.testing.assert_allclose(
                    out, w0[k] - n_workers * r,
                    err_msg=f"key {k} round {r}")

    _parallel([lambda kv=kv: train(kv) for kv in topo.workers])


def test_stress_push_pull_multi_server_parties():
    """Combined push_pull under the freshness-race stress configuration
    (2-server parties, sharded keys, many rounds): exact every round."""
    topo = Topology(servers_per_party=2, bigarray_bound=16).start(
        sync_global=True)
    try:
        keys = [0, 1, 2]
        w0 = {0: np.arange(40, dtype=np.float32),
              1: np.ones(8, np.float32) * 3,
              2: np.linspace(-5, 5, 33).astype(np.float32)}
        _stress_rounds_push_pull(topo, keys, w0, rounds=20, n_workers=4)
    finally:
        topo.stop()


def test_stress_push_pull_under_drop_and_resend():
    """Combined push_pull rounds under message loss + retransmit: a
    dropped/duplicated combined message must neither double-count a
    push nor lose its data-carrying ack (the client falls back to an
    explicit pull only when a server acks without data)."""
    topo = Topology(extra_cfg={"drop_rate": 0.05, "resend": True,
                               "resend_timeout_ms": 200}).start(
        sync_global=True)
    try:
        keys = [0, 1]
        w0 = {0: np.arange(24, dtype=np.float32),
              1: np.full(10, 2.0, np.float32)}
        _stress_rounds_push_pull(topo, keys, w0, rounds=8, n_workers=4)
    finally:
        topo.stop()
