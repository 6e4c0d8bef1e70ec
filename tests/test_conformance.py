"""State-model conformance sanitizer (geomx_tpu/ps/conformance.py).

Unit half: a StubVan drives StateSanitizer's hooks directly and proves
each divergence class latches (and that faithful transition reports
stay silent).

Van half: a real (unstarted) member Van processes DEAD_NODE / ADD_NODE
control messages with the sanitizer on — the live handlers and the
model must agree transition by transition. This also regression-tests
the table-adoption fix: a revival learned through the ADD_NODE table
broadcast must fire ``_membership_side_effects`` (countdown re-checks),
exactly like a DEAD_NODE adoption.

Recovery half: regression for the version-aware restore merge — a stale
snapshot must LOSE to a fresher peer replica (and win when it is the
fresher one).

Integration half: a real in-process tier runs a kill + zombie-fence
scenario with ``state_sanitizer=True`` on every van; the run must end
with zero violations on every statecheck, and the flight-recorder dumps
it leaves behind must replay clean through tools/modelcheck.py.
"""

import json
import os
import threading
import time
import types

import numpy as np
import pytest

from geomx_tpu import checkpoint
from geomx_tpu.ps.conformance import MARKER, StateSanitizer
from tests.harness import (
    SingleTier, _kill, _parallel, _round, _wait_declared)

assert MARKER  # the grep target scripts/run_chaos_matrix.sh fails on


class StubVan:
    def __init__(self, scheduler=False):
        self.is_scheduler = scheduler
        self.my_id = 1 if scheduler else 8
        self.flightrec = None


# ---------------------------------------------------------------------------
# unit: hook-level latching
# ---------------------------------------------------------------------------

def test_faithful_member_transitions_stay_silent():
    san = StateSanitizer(StubVan())
    san.on_dead_node(1, {11}, "adopt", (1, frozenset({11})))
    san.on_dead_node(1, {11}, "duplicate", (1, frozenset({11})))
    san.on_dead_node(0, set(), "stale", (1, frozenset({11})))
    san.on_fence(11, 0, True)            # dead -> stale: model agrees
    san.on_table(2, [11], (2, frozenset()))   # revival via table
    san.on_fence(11, 1, True)            # old-epoch zombie stays fenced
    san.on_fence(11, 2, False)           # rejoined incarnation passes
    assert san.report() == []


def test_outcome_divergence_latches(caplog):
    san = StateSanitizer(StubVan())
    san.on_dead_node(1, {11}, "adopt", (1, frozenset({11})))
    with caplog.at_level("ERROR", logger="geomx.conformance"):
        # a re-delivered broadcast the model calls "duplicate"
        san.on_dead_node(1, {11}, "adopt", (1, frozenset({11, 13})))
    assert any("outcome diverged" in v for v in san.violations)
    assert MARKER in caplog.text


def test_post_state_divergence_latches():
    san = StateSanitizer(StubVan())
    san.on_dead_node(1, {11}, "adopt", (1, frozenset({11, 12})))
    assert any("post-state diverged" in v for v in san.violations)


def test_declare_divergence_latches():
    san = StateSanitizer(StubVan(scheduler=True))
    san.on_declare([11], 1, frozenset({11}))       # faithful
    san.on_declare([12], 5, frozenset({11, 12}))   # epoch jumped to 5
    assert len(san.violations) == 1
    assert "declare_dead diverged" in san.violations[0]


def test_revive_divergence_latches():
    san = StateSanitizer(StubVan(scheduler=True))
    san.on_declare([11], 1, frozenset({11}))
    san.on_revive(11, 2)                 # faithful
    san.on_declare([12], 3, frozenset({12}))
    assert san.violations == []
    san.on_revive(12, 99)                # wrong epoch (model: 4)
    assert any("revive(12) diverged" in v for v in san.violations)


def test_fence_divergence_latches():
    san = StateSanitizer(StubVan())
    san.on_fence(9, 0, True)             # van fences a live sender
    assert any("is_stale(9, epoch=0) diverged" in v
               for v in san.violations)


def test_release_requires_fence_pass():
    san = StateSanitizer(StubVan())
    san.on_fence(9, 0, False)
    san.on_release(0, {(9, 0)})          # passed the fence: fine
    assert san.violations == []
    san.on_release(0, {(10, 0)})         # never fence-checked
    assert any("never passed the is_stale fence" in v
               for v in san.violations)


def test_restore_after_serving_latches():
    san = StateSanitizer(StubVan())
    san.on_restore("snapshot", served=False)
    assert san.violations == []
    san.on_restore("replica", served=True)
    assert any("AFTER the server started serving" in v
               for v in san.violations)


def test_report_is_idempotent(caplog):
    san = StateSanitizer(StubVan())
    san.on_fence(9, 0, True)
    assert len(san.report()) == 1
    assert len(san.on_shutdown()) == 1   # second report: no re-log
    assert len(san.violations) == 1


# ---------------------------------------------------------------------------
# van-level: real handlers against the mirror
# ---------------------------------------------------------------------------

def _member_van():
    from geomx_tpu.ps.message import Role
    from geomx_tpu.ps.van import Van

    van = Van(my_role=Role.WORKER, is_global=False,
              root_uri="127.0.0.1", root_port=1, num_workers=2,
              num_servers=1, state_sanitizer=True)
    van.my_id = 9
    van.my_port = 0      # normally assigned at bind time
    return van


def _msg(epoch, nodes):
    from geomx_tpu.ps.message import Message, Meta

    return Message(Meta(epoch=epoch, nodes=nodes))


def test_member_van_conforms_and_table_adoption_fires_side_effects():
    from geomx_tpu.ps.message import Node

    van = _member_van()
    events = []
    van.on_membership = lambda epoch, dead: events.append(
        (epoch, frozenset(dead)))

    # DEAD_NODE adoption
    van._process_dead_node(_msg(1, [Node(id=11)]))
    assert van.membership_epoch == 1
    assert events == [(1, frozenset({11}))]
    # duplicate and stale broadcasts: no re-fire, still conformant
    van._process_dead_node(_msg(1, [Node(id=11)]))
    van._process_dead_node(_msg(0, []))
    assert events == [(1, frozenset({11}))]

    # the regression: a revival learned ONLY via the ADD_NODE table
    # broadcast must fire the membership side effects (countdown
    # re-checks) — before the fix this hook never fired here
    van._process_add_node(_msg(2, [Node(id=11, hostname="127.0.0.1",
                                        port=5, is_recovery=True)]))
    assert van.membership_epoch == 2
    assert van._rejoin_epoch[11] == 2
    assert events == [(1, frozenset({11})), (2, frozenset())]

    # an initial (unchanged) table broadcast must NOT fire side effects
    van._process_add_node(_msg(2, [Node(id=11, hostname="127.0.0.1",
                                        port=5, is_recovery=True)]))
    assert events == [(1, frozenset({11})), (2, frozenset())]

    # fences agree with the model throughout
    assert van.is_stale(11, 1) and not van.is_stale(11, 2)
    assert van.statecheck.report() == []


def test_out_of_band_mutation_is_caught():
    """The runtime dual of GX-S502: membership state mutated outside a
    modeled transition desynchronizes the mirror — the next faithful
    transition exposes it."""
    from geomx_tpu.ps.message import Node

    van = _member_van()
    van._process_dead_node(_msg(1, [Node(id=11)]))
    assert van.statecheck.violations == []

    van._declared_dead.add(13)           # rogue out-of-band mutation

    # the same broadcast again: the van sees a CHANGED set and adopts;
    # the model knows it is a duplicate
    van._process_dead_node(_msg(1, [Node(id=11)]))
    assert any("diverged" in v for v in van.statecheck.violations)


# ---------------------------------------------------------------------------
# recovery: version-aware snapshot-vs-replica merge
# ---------------------------------------------------------------------------

def _image(version, value):
    entries = {(0, 0): {"v": np.full(4, value, np.float32),
                        "total": 4, "version": version,
                        "rounds": version}}
    return checkpoint.serialize_blob({
        "entries": checkpoint.serialize_states(entries),
        "updater": b"", "updater_states": b"", "flags": {}})


def _stub_replication(tmp_path, snapshot_version, replica_version):
    from geomx_tpu.kvstore.replication import ReplicationManager

    def mkstate():
        return types.SimpleNamespace(
            lock=threading.Lock(), stored=None, length=0, total=0,
            dtype=np.float32, version=0, rounds=0, initialized=False)

    states = {}
    server = types.SimpleNamespace(
        is_global_server=False,
        po_global=None,
        po_local=types.SimpleNamespace(
            my_rank=0, num_servers=2,
            van=types.SimpleNamespace(statecheck=None)),
        _ready=threading.Event(),
        _lock=threading.Lock(),
        _key_total={},
        _state=lambda key, off: states.setdefault((key, off), mkstate()),
        updater=None,
    )
    cfg = types.SimpleNamespace(snapshot_dir=str(tmp_path),
                                snapshot_interval_s=1.0, replicate=True)
    rep = ReplicationManager(server, cfg)
    with open(rep.path(), "wb") as f:
        f.write(_image(snapshot_version, 1.0))
    rep._fetch_from_peer = lambda timeout=60.0: _image(replica_version, 2.0)
    return rep, states


def test_restore_prefers_fresher_replica(tmp_path):
    """The fix: a snapshot written a tick ago must lose to the peer's
    replica when the replica carries more released rounds."""
    rep, states = _stub_replication(tmp_path, snapshot_version=1,
                                    replica_version=3)
    assert rep.restore() == "replica"
    assert rep.restored_from == "replica"
    st = states[(0, 0)]
    assert st.version == 3
    np.testing.assert_allclose(st.stored, np.full(4, 2.0, np.float32))


def test_restore_keeps_snapshot_when_fresher_or_tied(tmp_path):
    rep, states = _stub_replication(tmp_path, snapshot_version=3,
                                    replica_version=3)
    assert rep.restore() == "snapshot"   # tie: local snapshot wins
    assert states[(0, 0)].version == 3
    np.testing.assert_allclose(states[(0, 0)].stored,
                               np.full(4, 1.0, np.float32))


# ---------------------------------------------------------------------------
# integration: kill + zombie fence under the sanitizer
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_membership_churn_with_state_sanitizer_clean(tmp_path, caplog):
    """A worker dies (declared by fiat — the partition case), keeps
    pushing as a zombie, the survivor finishes its round sized to the
    live view. Every van runs the conformance sanitizer; the run must
    end with zero violations, and the flight-recorder dumps must replay
    clean through the offline checker."""
    from geomx_tpu.optimizer import SGD
    from tools.modelcheck import replay_paths

    w0 = np.full(8, 10.0, np.float32)
    with SingleTier(extra={"state_sanitizer": True,
                           "flightrec_dir": str(tmp_path)}) as topo:
        rank0 = next(kv for kv in topo.workers if kv.rank == 0)
        zombie = next(kv for kv in topo.workers if kv.rank == 1)
        rank0.set_optimizer(SGD(learning_rate=1.0))
        _parallel([lambda kv=kv: kv.init(0, w0) for kv in topo.workers])
        _parallel([lambda kv=kv: _round(kv, 0, w0, w0 - 2.0)
                   for kv in topo.workers])

        vans = [topo.sched_po.van, topo.server.po_local.van,
                rank0.po.van, zombie.po.van]
        assert all(v.statecheck is not None for v in vans)

        zid = zombie.po.my_id
        topo.sched_po.van.declare_dead([zid])
        _wait_declared([rank0.po.van, topo.server.po_local.van], zid)

        # fenced zombie push (never acked; we don't wait on it)
        zombie.push(0, np.full_like(w0, 100.0))
        time.sleep(0.5)

        # survivor's round releases against the live view
        _round(rank0, 0, w0, w0 - 3.0)

        # force a dump from every van so the replay half has real rings
        for v in vans:
            v.flightrec.dump("test-conformance")

        _kill(zombie)

    for v in vans:
        assert v.statecheck.violations == [], (
            f"van {v.my_id}: {v.statecheck.violations}")
    assert MARKER not in caplog.text

    # offline replay over the rings this run left behind
    from pathlib import Path

    report = replay_paths([Path(tmp_path)])
    assert report["files"], "no flightrec dumps were written"
    assert report["violations"] == 0, json.dumps(report, indent=1)


def test_crashed_van_barrier_fails_fast():
    """A stopped (crashed) van can neither deliver a barrier request nor
    receive the release: barrier() must refuse immediately instead of
    parking the caller for the full timeout — a chaos-crashed worker's
    atexit path would otherwise bleed out serially through it."""
    van = _member_van()
    van.stop()
    t0 = time.monotonic()
    with pytest.raises(OSError):
        van.barrier(group=7, timeout=60.0)
    assert time.monotonic() - t0 < 1.0
