"""Failure detection + elastic recovery, end-to-end.

Reference behavior (3rdparty/ps-lite/src/van.cc:176-193): when a node
re-registers and a registered node of the same role has missed its
heartbeats, the scheduler hands the dead slot's id to the newcomer with
``is_recovery=True`` and re-broadcasts the topology; recovering nodes
skip startup barriers (kvstore_dist.h:63). Server state is NOT persisted
(SURVEY.md §5.4) — resume after a server death is re-init + recovery.

These tests kill a node mid-training (hard van stop — no goodbye), wait
for heartbeat lapse, revive it, and assert id handover plus correct
values on resumed training. Single-tier PS topology (the reference's
global-tier recovery is explicitly unimplemented: van.cc:224 TODO).
"""

import json
import time

import numpy as np
import pytest

from geomx_tpu.kvstore.server import KVStoreDistServer
from geomx_tpu.optimizer import SGD
from geomx_tpu.ps import base as psbase
from tests.harness import (
    DEADLINES, HB, SingleTier, _Background, _kill, _parallel, _poll, _round,
    _wait_dead)

# -- worker rejoin (docs/robustness.md, "Elastic membership" steps 1-5) ----

KEYS = [0, 1]
W0 = {0: np.full(12, 10.0, np.float32), 1: np.full(5, -3.0, np.float32)}


def _ones():
    return [np.ones_like(W0[k]) for k in KEYS]


def _round_per_key(kv):
    for k, g in zip(KEYS, _ones()):
        kv.push(k, g)
    outs = [np.zeros_like(W0[k]) for k in KEYS]
    for k, o in zip(KEYS, outs):
        kv.pull(k, out=o)
    kv.wait()
    return outs


def _round_batched(kv):
    kv.push(KEYS, _ones())
    outs = [np.zeros_like(W0[k]) for k in KEYS]
    kv.pull(KEYS, out=outs)
    kv.wait()
    return outs


def _round_push_pull(kv):
    outs = [np.zeros_like(W0[k]) for k in KEYS]
    kv.push_pull(KEYS, _ones(), out=outs)
    kv.wait()
    return outs


WIRES = {"per_key": _round_per_key, "batched": _round_batched,
         "push_pull": _round_push_pull}


def _assert_pushes_applied(outs, n, who):
    """SGD(lr=1) over unit gradients: the weights read W0 - n once the
    server has applied n pushes in all."""
    for k, o in zip(KEYS, outs):
        np.testing.assert_allclose(
            o, W0[k] - n, err_msg=f"{who}: key {k} must carry {n} pushes")


@pytest.mark.parametrize("phase", ["within_grace", "after_declaration"])
@pytest.mark.parametrize("wire", list(WIRES))
def test_worker_rejoin(wire, phase):
    """A worker dies with its partner's round open, and a replacement
    takes its slot (reference: ps-lite van.cc:176-193), over each wire.

    ``within_grace``: the lapse is not yet declared (``epoch_grace_s``
    outlasts the test), so the round stays open, the replacement joins
    THAT round and both read two more pushes. ``after_declaration``: the
    declaration shrinks the server's live view, the open round is
    released with the survivor's push alone, and the replacement joins
    the next one. Ordered by events the test polls for, never by sleeps.
    """
    do_round = WIRES[wire]
    within_grace = phase == "within_grace"
    extra = {"epoch_grace_s": 10 * DEADLINES["lifetime_s"]} \
        if within_grace else None
    with SingleTier(extra=extra) as topo:
        rank0, victim = sorted(topo.workers, key=lambda kv: kv.rank)
        server = topo.server
        rank0.set_optimizer(SGD(learning_rate=1.0))
        _parallel([lambda kv=kv: [kv.init(k, W0[k]) for k in KEYS]
                   for kv in topo.workers])
        for outs in _parallel([lambda kv=kv: do_round(kv)
                               for kv in topo.workers]):
            _assert_pushes_applied(outs, 2, "round 1")

        # round 2 opens with the survivor's push; its partner's never comes
        survivor = _Background(lambda: do_round(rank0))
        states = [server._states[(k, 0)] for k in KEYS]
        _poll(lambda: all(len(st.push_reqs) == 1 for st in states),
              "the survivor's pushes to reach the server")
        dead_id = victim.po.my_id
        _kill(victim)
        _wait_dead(topo, dead_id)        # the slot is now up for handover

        if within_grace:
            assert dead_id not in topo.sched_po.van.declared_dead_ids()
            assert server.po_local.num_live_workers() == 2
            assert all(len(st.push_reqs) == 1 for st in states)
            assert not survivor.done(), "the round must wait for the pardon"
        else:
            _poll(lambda: server.po_local.num_live_workers() == 1,
                  "the server's live view to shrink")
            _assert_pushes_applied(survivor.result(), 3, "released round")

        revived = topo.revive_worker()
        assert revived.po.van.is_recovery, "scheduler did not hand over slot"
        assert revived.po.my_id == dead_id
        assert revived.rank == 1
        # the table broadcast reaches members in turn, and the server
        # fences data from a declared id until it has un-declared it
        _poll(lambda: server.po_local.num_live_workers() == 2,
              "the server to admit the replacement")
        for k in KEYS:
            revived.init(k, W0[k])       # acked and ignored: store is live

        if within_grace:
            _assert_pushes_applied(do_round(revived), 4, "joined round")
            _assert_pushes_applied(survivor.result(), 4, "open round")
        for outs in _parallel([lambda kv=kv: do_round(kv)
                               for kv in (rank0, revived)]):
            _assert_pushes_applied(outs, 6 if within_grace else 5,
                                   "the pair's round")


def test_server_dies_and_recovers_mid_training():
    """Server store is volatile (reference: SURVEY §5.4): after the slot
    handover, workers re-init and re-ship the optimizer, then training
    resumes from the re-initialized weights."""
    w0 = np.full(8, 4.0, np.float32)
    with SingleTier() as topo:
        rank0 = next(kv for kv in topo.workers if kv.rank == 0)
        rank0.set_optimizer(SGD(learning_rate=1.0))
        _parallel([lambda kv=kv: kv.init(0, w0) for kv in topo.workers])
        _parallel([lambda kv=kv: _round(kv, 0, w0, w0 - 2.0)
                   for kv in topo.workers])

        dead_id = topo.server.po_local.my_id
        topo.server.crash()              # hard kill: no barrier
        _wait_dead(topo, dead_id)

        revived = topo.revive_server()
        assert revived.po_local.van.is_recovery
        assert revived.po_local.my_id == dead_id

        # resume: re-init (store was volatile), re-ship the optimizer
        rank0.set_optimizer(SGD(learning_rate=1.0))
        _parallel([lambda kv=kv: kv.init(0, w0) for kv in topo.workers])
        _parallel([lambda kv=kv: _round(kv, 0, w0, w0 - 2.0)
                   for kv in topo.workers])


# ----------------------------------------------------------------------
# durable recovery (kvstore/replication.py): a revived server serves
# PRE-CRASH values — beyond the reference, whose store is volatile
# ----------------------------------------------------------------------


def _pull_now(kv, key, like):
    out = np.zeros_like(like)
    kv.pull(key, out=out)
    kv.wait()
    return out


def test_server_recovers_state_from_snapshot(tmp_path):
    """Durable recovery, single tier: the server dies AFTER training made
    progress; the replacement restores weights + optimizer from its
    periodic snapshot and serves the PRE-CRASH values with NO re-init and
    NO optimizer re-ship (contrast: test_server_dies_and_recovers_mid_
    training above documents the old volatile-store behavior)."""
    w0 = np.full(8, 4.0, np.float32)
    with SingleTier(extra={"snapshot_dir": str(tmp_path),
                           "snapshot_interval_s": 0.1}) as topo:
        rank0 = next(kv for kv in topo.workers if kv.rank == 0)
        rank0.set_optimizer(SGD(learning_rate=1.0))
        _parallel([lambda kv=kv: kv.init(0, w0) for kv in topo.workers])
        for r in (1, 2):
            _parallel([lambda kv=kv, r=r: _round(kv, 0, w0, w0 - 2.0 * r)
                       for kv in topo.workers])
        time.sleep(0.5)                  # several snapshot ticks
        assert topo.server.replication.num_snapshots > 0

        dead_id = topo.server.po_local.my_id
        topo.server.crash()              # hard kill: no flush, no barrier
        _wait_dead(topo, dead_id)

        revived = topo.revive_server()
        assert revived.po_local.van.is_recovery
        assert revived.po_local.my_id == dead_id
        assert revived.replication.restored_from == "snapshot"

        # pre-crash weights, straight from the restored store
        for kv in topo.workers:
            np.testing.assert_allclose(_pull_now(kv, 0, w0), w0 - 4.0)
        # training continues (restored updater applies round 3)
        _parallel([lambda kv=kv: _round(kv, 0, w0, w0 - 6.0)
                   for kv in topo.workers])


def test_server_recovers_state_from_peer_replica():
    """Diskless multi-server recovery: NO snapshot dir — each server
    replicates its dirty state to the next-rank peer every tick, and the
    revived server restores by fetching its replica from that peer
    (Command.REPLICA_FETCH)."""
    w0 = np.full(8, 4.0, np.float32)
    with SingleTier(extra={"snapshot_interval_s": 0.1},
                    num_servers=2) as topo:
        rank0 = next(kv for kv in topo.workers if kv.rank == 0)
        rank0.set_optimizer(SGD(learning_rate=1.0))
        _parallel([lambda kv=kv: kv.init(0, w0) for kv in topo.workers])
        for r in (1, 2):
            _parallel([lambda kv=kv, r=r: _round(kv, 0, w0, w0 - 2.0 * r)
                       for kv in topo.workers])
        time.sleep(0.6)                  # replica deltas propagate

        # the victim is whichever server actually holds key 0's shard
        from geomx_tpu.kvstore import sharding

        owner = sharding.assign(0, w0.size, 2,
                                topo._cfg().bigarray_bound)[0].server_rank
        victim = next(s for s in topo.servers
                      if s.po_local.my_rank == owner)
        dead_id = victim.po_local.my_id
        victim.crash()
        _wait_dead(topo, dead_id)

        revived = topo.revive_server()
        assert revived.po_local.van.is_recovery
        assert revived.po_local.my_id == dead_id
        assert revived.replication.restored_from == "replica"

        for kv in topo.workers:
            np.testing.assert_allclose(_pull_now(kv, 0, w0), w0 - 4.0)
        _parallel([lambda kv=kv: _round(kv, 0, w0, w0 - 6.0)
                   for kv in topo.workers])


def test_hips_party_server_recovers_state(tmp_path):
    """Two-tier HiPS: a party server dies between rounds; its replacement
    restores the party's cached model from its snapshot and serves the
    pre-crash values, then a full cross-party round completes."""
    from geomx_tpu.simulate import InProcessHiPS

    extra = dict(HB)
    extra.update(snapshot_dir=str(tmp_path), snapshot_interval_s=0.1)
    sim = InProcessHiPS(num_parties=2, workers_per_party=1,
                        extra_cfg=extra)
    sim.start(sync_global=True)
    try:
        w0 = np.full(6, 8.0, np.float32)
        sim.master.set_optimizer(SGD(learning_rate=1.0))
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in sim.workers + [sim.master]])

        def step(kv, r):
            kv.push(0, np.ones_like(w0))
            out = np.zeros_like(w0)
            kv.pull(0, out=out)
            kv.wait()
            np.testing.assert_allclose(out, w0 - 2.0 * r)

        for r in (1, 2):
            sim.run_workers(lambda kv, r=r: step(kv, r))
        time.sleep(0.5)                  # snapshot ticks on every server

        # kill the SECOND party's server (servers[0] is the global server)
        victim = sim.servers[2]
        assert not victim.is_global_server
        victim.crash()
        time.sleep(3.0)                  # heartbeat lapse on BOTH tiers

        revived = KVStoreDistServer(victim.cfg)
        sim._spawn(revived.run)
        _poll(lambda: sim.errors or revived._ready.is_set(),
              "the revived party server to become ready",
              DEADLINES["start_s"])
        assert not sim.errors, sim.errors
        assert revived.po_local.van.is_recovery
        assert revived.po_global is not None
        assert revived.po_global.van.is_recovery
        assert revived.replication.restored_from == "snapshot"

        # the party behind the revived server sees pre-crash values
        kv1 = sim.workers[1]
        out = np.zeros_like(w0)
        kv1.pull(0, out=out)
        kv1.wait()
        np.testing.assert_allclose(out, w0 - 4.0)

        # and a full cross-party round still completes exactly
        sim.run_workers(lambda kv: step(kv, 3))
        sim.servers[2] = revived
    finally:
        sim.stop()


@pytest.mark.chaos
def test_faultplan_crash_resume_matches_uninterrupted(tmp_path):
    """THE acceptance scenario: run A trains 3 rounds uninterrupted; run
    B is identical but a FaultPlan crash primitive kills the server on
    the first data frame of round 3. The replacement restores from the
    periodic snapshot, the workers' retransmits complete round 3, and
    the final pulled weights EQUAL run A's — restored from state, not
    re-initialized (no re-init or optimizer re-ship happens in run B
    after the crash)."""
    w0 = np.full(8, 4.0, np.float32)
    common = {
        "snapshot_dir": None,            # per-run below
        "snapshot_interval_s": 0.1,
        "resend": True,
        "resend_timeout_ms": 2000,       # generous: no spurious resends
        "ps_seed": 7,
        # crash->revival must win the race against the DEAD_NODE
        # broadcast: a declaration between the crash and the
        # replacement's registration fail-fasts the workers' pending
        # round-3 pushes ("peer declared dead") instead of letting them
        # retransmit to the revived slot. The recovery handover itself
        # keys off the heartbeat-lapse scan, not the declared set, so a
        # generous grace only defers the broadcast — on a loaded 1-core
        # box the replacement can need several seconds to register.
        "epoch_grace_s": 30.0,
    }
    server_id = psbase.server_rank_to_id(0)

    def train_two_rounds(topo):
        rank0 = next(kv for kv in topo.workers if kv.rank == 0)
        rank0.set_optimizer(SGD(learning_rate=1.0))
        _parallel([lambda kv=kv: kv.init(0, w0) for kv in topo.workers])
        for r in (1, 2):
            _parallel([lambda kv=kv, r=r: _round(kv, 0, w0, w0 - 2.0 * r)
                       for kv in topo.workers])
        time.sleep(0.5)                  # quiesce + snapshot ticks

    # -- run A: uninterrupted baseline ---------------------------------
    extra_a = dict(common, snapshot_dir=str(tmp_path / "a"))
    del extra_a["ps_seed"]               # seedless is fine without a plan
    with SingleTier(extra=extra_a) as topo_a:
        train_two_rounds(topo_a)
        # data frames the server received through rounds 1-2: the crash
        # point for run B is the NEXT one (round 3's first arrival)
        n_pre = topo_a.server.po_local.van.num_data_recv
        final_a = _parallel([lambda kv=kv: _pull_now(kv, 0, w0)
                             for kv in topo_a.workers])
        _parallel([lambda kv=kv: _round(kv, 0, w0, w0 - 6.0)
                   for kv in topo_a.workers])
        expect = w0 - 6.0
    np.testing.assert_allclose(final_a[0], w0 - 4.0)

    # -- run B: same training, server crashed by the fault plan --------
    plan = json.dumps({"rules": [{
        "type": "crash", "node": server_id, "at": n_pre + 1,
        "on": "recv", "tier": "local"}]})
    extra_b = dict(common, snapshot_dir=str(tmp_path / "b"),
                   fault_plan=plan)
    with SingleTier(extra=extra_b) as topo_b:
        train_two_rounds(topo_b)
        dead_id = topo_b.server.po_local.my_id
        assert dead_id == server_id

        # round 3: the first data frame trips the crash rule
        def round3(kv):
            kv.push(0, np.ones_like(w0))
            out = np.zeros_like(w0)
            kv.pull(0, out=out)
            kv.wait()
            return out

        rounds = [_Background(lambda kv=kv: round3(kv))
                  for kv in topo_b.workers]
        _wait_dead(topo_b, dead_id)
        assert topo_b.server._crashed, "FaultPlan crash did not fire"

        # the replacement gets NO fault plan (fresh host) but the same
        # snapshot dir; workers' retransmits then complete round 3
        revived = topo_b.revive_server(fault_plan="")
        assert revived.po_local.van.is_recovery
        assert revived.replication.restored_from == "snapshot", \
            "run B must resume from the snapshot, not re-init"
        for kv, bg in zip(topo_b.workers, rounds):
            np.testing.assert_allclose(bg.result(), expect, err_msg=(
                f"worker {kv.rank}: resumed weights diverge from the "
                f"uninterrupted run"))
