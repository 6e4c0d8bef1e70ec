"""Elastic membership: epochs, zombie fencing, survivor progress.

The reference's scheduler only LOGS heartbeat lapses and hands the dead
id to the next registrant (van.cc:176-193); nothing tells the survivors,
so a synchronous round sized for N workers waits forever on a corpse's
push. These tests cover the membership-epoch layer built on top
(docs/robustness.md "Elastic membership"): a sustained heartbeat lapse
becomes a DEAD_NODE declaration that every member converges on, servers
re-size pending aggregation countdowns to the live view, and pushes from
declared-dead (but still running) zombies are fenced by epoch.
"""

import json
import time

import numpy as np
import pytest

from geomx_tpu.optimizer import SGD
from geomx_tpu.ps import base as psbase
from tests.harness import (
    SingleTier, _Background, _kill, _parallel, _poll, _round, _wait_dead,
    _wait_declared)


def test_heartbeat_lapse_declares_dead_and_bumps_epoch():
    """Heartbeat lapse -> dead_nodes() -> declaration: the scheduler
    promotes the lapse to a DEAD_NODE broadcast (epoch bump) and every
    surviving member's van converges on the same dead set + epoch."""
    w0 = np.full(6, 2.0, np.float32)
    with SingleTier() as topo:
        rank0 = next(kv for kv in topo.workers if kv.rank == 0)
        victim = next(kv for kv in topo.workers if kv.rank == 1)
        rank0.set_optimizer(SGD(learning_rate=1.0))
        _parallel([lambda kv=kv: kv.init(0, w0) for kv in topo.workers])

        dead_id = victim.po.my_id
        _kill(victim)

        # raw heartbeat lapse first (the pre-existing detector)...
        _wait_dead(topo, dead_id)
        # ...then the declaration (grace is 0: promoted on the next tick)
        sched_van = topo.sched_po.van
        _wait_declared([sched_van], dead_id)
        assert sched_van.membership_epoch >= 1
        assert dead_id not in sched_van.live_ids()

        # the broadcast reaches the survivor worker AND the server
        members = [rank0.po.van, topo.server.po_local.van]
        _wait_declared(members, dead_id)
        for v in members:
            assert v.membership_epoch >= 1
            assert dead_id not in v.live_ids()

        # the postoffice live view + dead-node counters follow
        assert topo.server.po_local.num_live_workers() == 1
        assert dead_id not in topo.server.po_local.live_worker_ids()
        assert rank0.get_num_dead_node() == 1
        assert rank0.get_num_dead_node(role="worker") == 1
        assert rank0.get_num_dead_node(role="server") == 0
        assert rank0.membership_epoch() >= 1


def test_stale_epoch_push_is_dropped():
    """Zombie fencing: a node the scheduler declared dead while it is
    STILL RUNNING (a partition, not a death) keeps pushing — the server
    must drop those pushes unacked instead of aggregating them."""
    w0 = np.full(8, 10.0, np.float32)
    with SingleTier() as topo:
        rank0 = next(kv for kv in topo.workers if kv.rank == 0)
        zombie = next(kv for kv in topo.workers if kv.rank == 1)
        rank0.set_optimizer(SGD(learning_rate=1.0))
        _parallel([lambda kv=kv: kv.init(0, w0) for kv in topo.workers])
        _parallel([lambda kv=kv: _round(kv, 0, w0, w0 - 2.0)
                   for kv in topo.workers])

        # declare the rank-1 worker dead by fiat (its heartbeats are
        # fine — this is the false-positive/partition case)
        zid = zombie.po.my_id
        topo.sched_po.van.declare_dead([zid])
        _wait_declared([rank0.po.van, topo.server.po_local.van], zid)

        # the zombie pushes a poison gradient; fenced -> no aggregation,
        # no ack (we never wait on it)
        zombie.push(0, np.full_like(w0, 100.0))
        time.sleep(0.5)

        # the survivor's round is sized to the live view (1 worker) and
        # must see ONLY its own gradient: -1, not -101
        _round(rank0, 0, w0, w0 - 3.0)

        # the poison push must not even have bumped the round version
        assert topo.server._states[(0, 0)].version == 2  # rounds 1+2 only
        _kill(zombie)                    # it could not leave by the door


@pytest.mark.chaos
def test_three_workers_lose_one_mid_round_survivors_continue():
    """THE acceptance scenario: 3 workers under a seeded FaultPlan whose
    crash rule kills the rank-2 worker at the start of round 2 (the new
    ``at_round`` primitive, driven by kv.notify_round). The survivors'
    round must complete once the declaration lands (the server re-sizes
    the pending countdown from 3 to the 2 live workers), and the pair
    then trains >= 5 further rounds with the key version advancing."""
    plan = json.dumps({"rules": [{
        "type": "crash", "node": psbase.worker_rank_to_id(2),
        "at_round": 2, "tier": "local"}]})
    w0 = np.full(10, 30.0, np.float32)
    with SingleTier(num_workers=3,
                    extra={"fault_plan": plan, "ps_seed": 11}) as topo:
        workers = sorted(topo.workers, key=lambda kv: kv.rank)
        rank0 = workers[0]
        victim = workers[2]
        survivors = workers[:2]
        rank0.set_optimizer(SGD(learning_rate=1.0))
        _parallel([lambda kv=kv: kv.init(0, w0) for kv in workers])

        # round 1: everyone alive (sum of 3 unit gradients)
        for kv in workers:
            kv.notify_round(1)
        _parallel([lambda kv=kv: _round(kv, 0, w0, w0 - 3.0)
                   for kv in workers])

        # round 2: survivors push and block on the missing third push
        def survivor_round(kv):
            kv.notify_round(2)
            kv.push(0, np.ones_like(w0))
            out = np.zeros_like(w0)
            kv.pull(0, out=out)
            kv.wait()
            return out

        rounds = [_Background(lambda kv=kv: survivor_round(kv))
                  for kv in survivors]
        state = topo.server._states[(0, 0)]
        _poll(lambda: len(state.push_reqs) == 2,
              "the survivors' pushes to land: 2 of 3")
        dead_id = victim.po.my_id
        # the fault plan kills the victim's van at its round-2 entry: no
        # goodbye, no barrier, no push — indistinguishable from death
        victim.notify_round(2)
        assert victim.po.van.stopped.wait(10), \
            "at_round crash rule did not fire"

        # declaration -> the server releases the stalled round with the
        # survivors' gradients (no re-push, no timeout)
        for kv, bg in zip(survivors, rounds):
            np.testing.assert_allclose(bg.result(), w0 - 5.0, err_msg=(
                f"worker {kv.rank}: released round must carry exactly "
                f"the 2 survivor gradients"))
        _wait_declared([topo.server.po_local.van], dead_id)
        assert topo.server.po_local.num_live_workers() == 2

        # >= 5 subsequent rounds: versions keep advancing
        v_before = state.version
        for r in range(1, 6):
            _parallel([lambda kv=kv, r=r:
                       _round(kv, 0, w0, w0 - 5.0 - 2.0 * r)
                       for kv in survivors])
        assert state.version >= v_before + 5


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
