"""Mesh-party tier (``dist_sync_mesh``): parity with the wire path.

The tentpole claim (docs/mesh-party.md): replacing a party's LAN PS hop
with a GSPMD psum over the party mesh changes WHERE the intra-party
aggregation runs, not WHAT it computes. These tests prove it bit-exactly
on the CPU 8-virtual-device mesh (tests/conftest.py sets
XLA_FLAGS=--xla_force_host_platform_device_count=8):

- dense FSA: a 2-party x 2-member wire run and a 2-party mesh run
  (party_mesh_size=2) fed the same per-member data end with IDENTICAL
  weights after N rounds. Exactness is by construction: every input is
  an integer multiple of a power of two and magnitudes stay far below
  2^24, so fp32 addition is exact in ANY order — the device psum order
  vs the server's arrival-order sum cannot diverge.
- BSC: DeviceResidentTrainer over the mesh store (party batch sharded
  over "dp", psum inside grad_fn's backward) matches the same trainer
  fed the full party batch on one device, bit-exactly. Here values go
  through 0.9-momentum BSC buffers (inexact fp32), so parity rests on
  determinism: identical inputs -> identical device programs -> the
  global tier adds exactly TWO party aggregates, and two-operand fp32
  addition is commutative.
- chaos: the party whose server survives must not hang when a REMOTE
  party's server is killed mid-training — the round either completes
  from the released aggregation or aborts with the RoundAborted family
  within a bounded wait (RoundFuture.abort_pending fan-out).
"""

import threading
import time
import weakref

import numpy as np
import pytest

from geomx_tpu import telemetry
from geomx_tpu.kvstore.frontier import RoundAborted, RoundFuture
from geomx_tpu.kvstore.mesh_party import KVStorePartyMesh, _ring_bytes
from geomx_tpu.optimizer import SGD
from geomx_tpu.simulate import InProcessHiPS
from geomx_tpu.trainer import Trainer
from geomx_tpu.trainer_device import DeviceResidentTrainer
from tests.harness import DEADLINES, _Background, _wait_declared

ROUNDS = 5
SHAPES = [(4,), (2, 2)]
# per-(round, party, member) data: integers scaled by 2^-2 -> every
# gradient/weight below is an exact fp32 value (see module docstring)
_rng = np.random.RandomState(7)
DATA = [
    _rng.randint(-8, 9, size=(ROUNDS, 2, 2) + shp).astype(np.float32) * 0.25
    for shp in SHAPES
]


def _zeros():
    return [np.zeros(s, np.float32) for s in SHAPES]


def _master_init(kv):
    for i, w in enumerate(_zeros()):
        kv.init(i, w)
    kv.wait()


# -- dense FSA parity ------------------------------------------------------


def _run_wire_dense():
    """Baseline: 2 parties x 2 van workers, per-member host gradients
    (w - t)/2 — the party's two members sum to the party-mean gradient
    the mesh run computes on device."""
    sim = InProcessHiPS(num_parties=2, workers_per_party=2).start()
    out = {}
    try:
        sim.master.set_optimizer(SGD(learning_rate=0.25))
        time.sleep(0.5)

        def worker(kv):
            widx = sim.workers.index(kv)
            p, m = divmod(widx, 2)
            tr = Trainer(_zeros(), kv)
            for r in range(ROUNDS):
                w = tr.leaves
                grads = [((w[i] - DATA[i][r, p, m]) / 2).astype(np.float32)
                         for i in range(len(SHAPES))]
                tr.step(grads)
            out[widx] = [np.array(l) for l in tr.leaves]

        sim.run_workers(worker, include_master=_master_init, timeout=300)
    finally:
        sim.stop()
    return out


def _run_mesh_dense():
    """Mesh run: one KVStorePartyMesh per party over 2 devices; grads
    come out of a jitted value_and_grad whose mean over the dp-sharded
    batch IS the intra-party aggregation (XLA-inserted psum)."""
    import jax
    import jax.numpy as jnp

    def _loss(w0, w1, X0, X1):
        d0 = w0[None] - X0
        d1 = w1[None] - X1
        return 0.5 * (jnp.mean(jnp.sum(d0 * d0, axis=-1))
                      + jnp.mean(jnp.sum(d1 * d1, axis=(-2, -1))))

    gstep = jax.jit(jax.value_and_grad(_loss, argnums=(0, 1)))

    sim = InProcessHiPS(num_parties=2, workers_per_party=2,
                        party_mesh_size=2).start()
    out = {}
    try:
        sim.master.set_optimizer(SGD(learning_rate=0.25))
        time.sleep(0.5)

        def worker(kv):
            p = sim.workers.index(kv)
            assert kv.type == "dist_sync_mesh"
            assert kv.party_size == 2 and kv.num_workers == 1
            tr = Trainer(_zeros(), kv)
            for r in range(ROUNDS):
                w = tr.leaves
                wd = [kv.put_replicated(jnp.asarray(l)) for l in w]
                X0, X1 = kv.shard_batch(DATA[0][r, p], DATA[1][r, p])
                _loss_v, grads = gstep(wd[0], wd[1], X0, X1)
                tr.step([np.asarray(g) for g in grads])
            out[p] = [np.array(l) for l in tr.leaves]

        sim.run_workers(worker, include_master=_master_init, timeout=300)
    finally:
        sim.stop()
    return out


@pytest.mark.mesh
def test_dense_fsa_parity_bit_exact():
    was_enabled = telemetry.enabled()
    try:
        telemetry.reset()           # reset() also disables -> re-enable
        telemetry.enable(True)
        wire = _run_wire_dense()
        wire_snap = telemetry.snapshot()
        telemetry.reset()
        telemetry.enable(True)
        mesh = _run_mesh_dense()
        mesh_snap = telemetry.snapshot()
    finally:
        telemetry.reset()
        telemetry.enable(was_enabled)

    # every wire worker and every mesh party ends on the SAME bits
    ref = wire[0]
    for widx in range(4):
        for i in range(len(SHAPES)):
            np.testing.assert_array_equal(wire[widx][i], ref[i])
    for p in range(2):
        for i in range(len(SHAPES)):
            np.testing.assert_array_equal(mesh[p][i], ref[i])

    # and the weights actually moved (the parity is not vacuous)
    assert any(np.any(l != 0) for l in ref)

    # telemetry: the mesh tier's collectives are counted under
    # tier=mesh, excluded from wan_bytes, and the party members put
    # ZERO extra messages on the van — the mesh run's LAN traffic is
    # strictly below the wire run's (2 members collapsed into 1
    # van worker per party)
    assert telemetry.mesh_bytes(mesh_snap) > 0
    assert telemetry.mesh_bytes(wire_snap) == 0

    def _local_msgs(snap):
        return sum(v for k, v in snap["counters"].items()
                   if k.startswith("van.messages_sent{")
                   and "tier=local" in k)

    assert _local_msgs(mesh_snap) < _local_msgs(wire_snap)
    # wan_bytes counts only the global-tier van sends in both runs
    for snap in (wire_snap, mesh_snap):
        assert telemetry.wan_bytes(snap) > 0
        for key in snap["counters"]:
            if key.startswith("mesh."):
                assert "tier=mesh" in key


# -- BSC parity ------------------------------------------------------------


BSC_DIM = 8
BSC_ROUNDS = 5
_bsc_rng = np.random.RandomState(21)
# (round, party, member, dim) integer/4 batches
BSC_DATA = _bsc_rng.randint(-8, 9, size=(BSC_ROUNDS, 2, 2, BSC_DIM)
                            ).astype(np.float32) * 0.25


def _bsc_master_init(kv):
    kv.init(0, np.zeros(BSC_DIM, np.float32))
    kv.wait()


def _bsc_grad_fn(leaves, X, y):
    import jax.numpy as jnp

    w = leaves[0]
    d = w[None, :] - X
    return 0.5 * jnp.mean(jnp.sum(d * d, axis=-1)), [jnp.mean(d, axis=0)]


def _run_bsc_mesh(threshold):
    sim = InProcessHiPS(num_parties=2, workers_per_party=2,
                        party_mesh_size=2).start()
    out = {}
    try:
        def worker(kv):
            p = sim.workers.index(kv)
            tr = DeviceResidentTrainer(
                [np.zeros(BSC_DIM, np.float32)], kv, _bsc_grad_fn,
                threshold=threshold, learning_rate=0.25)
            for r in range(BSC_ROUNDS):
                # the party's full batch; _place_batch shards it over dp
                tr.step(BSC_DATA[r, p].reshape(2, BSC_DIM), None)
            out[p] = np.array(tr.leaves[0])

        sim.run_workers(worker, include_master=_bsc_master_init,
                        timeout=300)
    finally:
        sim.stop()
    return out


def _run_bsc_wire_partybatch(threshold):
    """Wire baseline shaped like the mesh run: ONE worker per party fed
    the party's FULL batch (2 members' rows) on a single device — the
    single-device mean it computes is the quantity the mesh run's psum
    produces."""
    sim = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    out = {}
    try:
        def worker(kv):
            p = sim.workers.index(kv)
            tr = DeviceResidentTrainer(
                [np.zeros(BSC_DIM, np.float32)], kv, _bsc_grad_fn,
                threshold=threshold, learning_rate=0.25)
            for r in range(BSC_ROUNDS):
                tr.step(BSC_DATA[r, p].reshape(2, BSC_DIM), None)
            out[p] = np.array(tr.leaves[0])

        sim.run_workers(worker, include_master=_bsc_master_init,
                        timeout=300)
    finally:
        sim.stop()
    return out


@pytest.mark.mesh
def test_bsc_parity_bit_exact():
    """DeviceResidentTrainer over dist_sync_mesh == the same trainer
    over dist_sync fed the identical party batch, bit for bit — through
    the full BSC machinery (momentum buffers, per-key top-k, packed
    int32 wire, residual feedback). threshold=1.0 keeps selection
    total (k=n) so the parity covers every coordinate every round."""
    wire = _run_bsc_wire_partybatch(threshold=1.0)
    mesh = _run_bsc_mesh(threshold=1.0)
    for p in range(2):
        np.testing.assert_array_equal(mesh[p], wire[p])
    np.testing.assert_array_equal(wire[0], wire[1])
    assert np.any(wire[0] != 0)


@pytest.mark.mesh
def test_bsc_sparse_threshold_replicas_identical():
    """Sparse selection (k=2 of 8): mesh parties still end bit-identical
    to each other (the aggregated selection both apply is the same
    wire payload)."""
    mesh = _run_bsc_mesh(threshold=0.25)
    np.testing.assert_array_equal(mesh[0], mesh[1])
    assert np.any(mesh[0] != 0)


# -- abort fan-out / chaos -------------------------------------------------


def test_abort_pending_unblocks_joiners_immediately():
    """RoundFuture.abort_pending fails every pending key NOW: a joiner
    blocked with a long timeout wakes with RoundAborted in well under a
    second, and already-completed keys keep their results."""
    fut = RoundFuture([0, 1, 2])
    fut.complete_key(0, "done")
    woke = {}

    def join():
        t0 = time.monotonic()
        try:
            fut.wait(timeout=30.0)
        except RoundAborted as e:
            woke["exc"] = e
        woke["elapsed"] = time.monotonic() - t0

    t = threading.Thread(target=join, daemon=True)
    t.start()
    time.sleep(0.1)
    fut.abort_pending("round aborted: remote server declared dead")
    t.join(5.0)
    assert not t.is_alive()
    assert isinstance(woke.get("exc"), RoundAborted)
    assert woke["elapsed"] < 5.0
    assert fut.done()


def test_fail_fast_pending_aborts_watched_futures():
    """The mesh store's round_abort_hook fans the inner store's round
    death out to every live future it issued (and only live ones — the
    WeakSet drops collected futures) and re-seeds the ring residual
    streams so stale quantization error never replays into the retry."""
    store = object.__new__(KVStorePartyMesh)
    store._live_futs = weakref.WeakSet()
    store._reducers = {}
    store._residual_reset_hooks = []
    resets = []
    store.register_residual_reset_hook(lambda: resets.append(1))
    fut = store._watch(RoundFuture([0, 1]))
    gone = store._watch(RoundFuture([7]))
    del gone    # collected -> must not be touched (nor crash the hook)
    store._fail_fast_pending("server 9 declared dead")
    with pytest.raises(RoundAborted):
        fut.wait(timeout=1.0)
    assert resets == [1]


def test_ring_bytes_model():
    assert _ring_bytes(1, 1000) == 0       # single-device party: no links
    assert _ring_bytes(2, 1000) == 2000
    assert _ring_bytes(4, 1000) == 6000


@pytest.mark.mesh
@pytest.mark.chaos
def test_mesh_party_survives_remote_server_kill():
    """Chaos-matrix case: the global worker's party keeps its server;
    a REMOTE party's server is killed mid-training. The surviving mesh
    party's round must not hang — it either completes once the global
    tier releases the stalled aggregation (elastic membership) or
    raises the RoundAborted family, within a bounded wait.

    Ordered by events, not by sleeps: the victim sits in two tiers, and
    each tier's scheduler sees its heartbeats lapse in its own time.
    The survivor's round ends on the GLOBAL tier's declaration; the
    replacement is started only once the victim's own party has
    declared it too, because a registration that reaches a scheduler
    before the lapse is a newcomer to it, not a handover of the slot."""
    from geomx_tpu.kvstore.server import KVStoreDistServer

    # 15 heartbeats to a time-out: a live node's heartbeat thread,
    # starved beside the suite's other workers, does not miss that many
    sim = InProcessHiPS(
        num_parties=2, workers_per_party=2, party_mesh_size=2,
        extra_cfg={"heartbeat_interval_s": 0.2,
                   "heartbeat_timeout_s": 3.0}).start()
    try:
        sim.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.zeros(6, np.float32)
        _g = np.ones(6, np.float32)

        def init_and_round(kv):
            kv.init(0, w0)
            outb = np.zeros_like(w0)
            kv.pull(0, out=outb)
            kv.wait()
            kv.push_pull(0, _g, outb, priority=0)
            kv.wait()

        sim.master.init(0, w0)
        sim.master.wait()
        sim.run_workers(init_and_round, timeout=DEADLINES["start_s"])

        # kill party 1's server (servers[0] is the global server);
        # party 0's mesh store keeps ITS server — the WAN gateway
        victim = sim.servers[2]
        assert not victim.is_global_server
        local_id = victim.po_local.my_id
        global_id = victim.po_global.my_id
        victim.crash()

        def survivor_round():
            outb = np.zeros_like(w0)
            try:
                sim.workers[0].push_pull(0, _g, outb, priority=0)
                sim.workers[0].wait(timeout=60.0)
                return "completed"
            except RoundAborted:
                return "aborted"
            except TimeoutError:
                return "timeout"

        survivor = _Background(survivor_round)
        survivor.join(90.0)
        assert survivor.done(), (
            "mesh party hung on the round after the remote server died")
        survivor.result(0.0)    # any other error of the round is the test's

        # the slot is up for handover once BOTH of the victim's tiers
        # have declared it: members learn it from their scheduler
        _wait_declared([sim.servers[0].po_global.van], global_id)
        _wait_declared([sim.workers[1].po.van], local_id)

        # revive the dead server so the shutdown cascade completes
        revived = KVStoreDistServer(victim.cfg)
        rt = threading.Thread(target=revived.run, daemon=True)
        rt.start()
        sim.threads.append(rt)
        sim.servers[2] = revived
        assert revived._ready.wait(DEADLINES["start_s"]), (
            "revived party server not ready")
        assert revived.po_local.van.is_recovery, (
            "the party's scheduler did not hand over the slot")
        assert revived.po_local.my_id == local_id
    finally:
        sim.stop()
