"""Self-tuning transport controller (kvstore/controller.py): the pure
decision step (bootstrap / noise-floor hysteresis / sustained squeeze /
detector bypass), the live slice-budget source and its clamp edges,
plan plumbing (wire_tag / wan_tag / geomx_top rendering), flight-
recorder replayability, bit-for-bit guards for controller-off, and the
e2e mid-run link squeeze on a shaped 2-party cluster.
"""

import json
import os
import random
import time

import numpy as np
import pytest

from geomx_tpu import telemetry
from geomx_tpu.config import Config
from geomx_tpu.kvstore import controller as ctrl
from geomx_tpu.kvstore.frontier import (auto_slice_bytes,
                                        slice_bytes_from_links)
from geomx_tpu.optimizer import SGD
from geomx_tpu.ps.flightrec import FlightRecorder
from geomx_tpu.ps.shaping import ShapeLink
from geomx_tpu.ps.tsengine import TSScheduler
from geomx_tpu.simulate import InProcessHiPS
from tools import geomx_top

from tests.harness import _parallel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPE_PLAN = os.path.join(REPO, "scripts", "shapes",
                          "wan2_50ms_100mbps.json")


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.reset()
    yield
    telemetry.reset()


# ---------------------------------------------------------------------------
# step_link: the pure per-round decision
# ---------------------------------------------------------------------------

def test_bootstrap_classifies_immediately():
    """Hysteresis guards CHANGES, not the first classification: a fresh
    link (no learned baseline) commits on its first evidence — thin
    below thin_mbps, fat at/above fat_mbps, and the fp16 floor for a
    measured link in between."""
    k = ctrl.Knobs()
    _, rec = ctrl.step_link(None, 5.0, 150.0, 0, False, k)
    assert (rec["codec"], rec["changed"], rec["reason"]) == \
        (ctrl.THIN_POLICY, True, "thin_bw")
    _, rec = ctrl.step_link(None, 200.0, 10.0, 0, False, k)
    assert (rec["codec"], rec["changed"], rec["reason"]) == \
        (ctrl.FAT_POLICY, True, "fat_bw")
    _, rec = ctrl.step_link(None, 25.0, 50.0, 0, False, k)
    assert (rec["codec"], rec["changed"], rec["reason"]) == \
        (ctrl.FAT_POLICY, True, "fp16_floor")
    # ... but the floor never overrides an existing assignment
    st, _ = ctrl.step_link(None, 5.0, 150.0, 0, False, k)
    _, rec = ctrl.step_link(st, 25.0, 50.0, 0, False, k)
    assert (rec["codec"], rec["changed"], rec["reason"]) == \
        (ctrl.THIN_POLICY, False, "dead_zone")


def test_no_evidence_never_classifies():
    k = ctrl.Knobs()
    st, rec = ctrl.step_link(None, 0.0, 50.0, 0, False, k)
    assert rec["reason"] == "no_evidence"
    assert st["codec"] is None and not rec["changed"]


def test_noisy_healthy_link_never_flaps():
    """The ISSUE bar: a noisy-but-healthy link whose dips stay within
    its own learned noise floor (the PR-13 convention: sigma from the
    link's measured variance) must NEVER trigger a codec change after
    its bootstrap classification."""
    k = ctrl.Knobs(thin_mbps=50.0)  # dips to 47-49 cross the static bar
    st, rec = ctrl.step_link(None, 60.0, 50.0, 0, False, k)
    assert rec["changed"] and rec["reason"] == "fp16_floor"
    for bw in (52, 68, 51, 69, 47, 66, 48, 62, 49, 65, 47):
        st, rec = ctrl.step_link(st, float(bw), 50.0, 0, False, k)
        assert not rec["changed"], rec
    assert st["codec"] == ctrl.FAT_POLICY
    # the dips were recognized as noise, not squeezes
    _, rec = ctrl.step_link(st, 47.0, 50.0, 0, False, k)
    assert rec["reason"] == "noise_dip"


def test_sustained_squeeze_converges_within_3_rounds_and_stays():
    k = ctrl.Knobs()
    st = None
    for _ in range(3):  # healthy fat baseline, codec committed
        st, _ = ctrl.step_link(st, 200.0, 10.0, 0, False, k)
    assert st["codec"] == ctrl.FAT_POLICY
    hist = []
    for _ in range(10):  # sustained squeeze: 200 -> 10 Mbps
        st, rec = ctrl.step_link(st, 10.0, 10.0, 0, False, k)
        hist.append(rec)
    switched = [i for i, r in enumerate(hist) if r["changed"]]
    assert switched and switched[0] < 3, hist  # within 3 rounds
    # ... and stays: exactly one change, thin policy from then on
    assert len(switched) == 1
    assert all(r["codec"] == ctrl.THIN_POLICY
               for r in hist[switched[0]:])
    # baseline froze during the squeeze (the drop must not erode its
    # own reference): still near the healthy 200
    assert st["base"] > 150.0


def test_degraded_latch_and_rtx_burst_bypass_persistence():
    k = ctrl.Knobs()
    st, _ = ctrl.step_link(None, 200.0, 10.0, 0, False, k)
    assert st["codec"] == ctrl.FAT_POLICY
    # a latched link_degraded switches NOW even at healthy bandwidth:
    # the detector already cleared its own noise floor
    _, rec = ctrl.step_link(dict(st), 180.0, 10.0, 0, True, k)
    assert (rec["codec"], rec["changed"], rec["reason"]) == \
        (ctrl.THIN_POLICY, True, "degraded")
    # same for a local retransmit burst
    _, rec = ctrl.step_link(dict(st), 180.0, 10.0, k.rtx_burst, False, k)
    assert (rec["codec"], rec["changed"], rec["reason"]) == \
        (ctrl.THIN_POLICY, True, "rtx_burst")


def test_replay_record_matches_step():
    """Each record embeds its pre-state: replaying any record standalone
    must reproduce the logged action exactly."""
    k = ctrl.Knobs()
    rng = random.Random(5)
    st = None
    for _ in range(60):
        bw = rng.choice((0.0, 10.0, 30.0, 60.0, 100.0, 160.0, 220.0))
        st, rec = ctrl.step_link(st, bw, rng.uniform(1, 200),
                                 rng.choice((0, 0, 0, 7)),
                                 rng.random() < 0.05, k)
        assert ctrl.replay_record(rec, k) == {
            "codec": rec["codec"], "changed": rec["changed"],
            "reason": rec["reason"]}


# ---------------------------------------------------------------------------
# slice budget: live-estimate source + clamp edges
# ---------------------------------------------------------------------------

def test_auto_slice_clamp_edges():
    assert auto_slice_bytes(100.0, 1.0) == 65536          # BDP 12.5KB
    assert auto_slice_bytes(200.0, 1000.0) == 4 << 20     # BDP 25MB
    mid = auto_slice_bytes(50.0, 100.0)                   # BDP 625KB
    assert 65536 < mid < (4 << 20) and mid == 625000


def test_slice_bytes_from_links_precedence_and_floor():
    # empty / unmeasured links contribute nothing: callers keep their
    # configured budget (precedence rule 2 only fires with evidence)
    assert slice_bytes_from_links([]) == 0
    assert slice_bytes_from_links([(50.0, 0.0)]) == 0
    # loopback exclusion: rtt under the floor never drives chunking
    assert slice_bytes_from_links([(0.2, 10000.0)],
                                  rtt_floor_ms=1.0) == 0
    # worst (highest-BDP) qualifying link wins
    assert slice_bytes_from_links(
        [(0.2, 10000.0), (50.0, 100.0), (150.0, 20.0)],
        rtt_floor_ms=1.0) == 625000
    # clamp edges survive the max() composition
    assert slice_bytes_from_links([(100.0, 1.0)]) == 65536
    assert slice_bytes_from_links([(200.0, 1000.0),
                                   (100.0, 1.0)]) == 4 << 20


def test_controller_slice_hold_band():
    est = _FakeEstimator({"8": _row(50.0, 100.0)})
    c = _controller(est)
    p1 = c.plan(1)
    assert p1.slice_bytes == 625000
    # a jittery +10% estimate stays inside the 25% hold band
    est.rows = {"8": _row(50.0, 110.0)}
    assert c.plan(2).slice_bytes == 625000
    # a real move re-publishes
    est.rows = {"8": _row(50.0, 300.0)}
    assert c.plan(3).slice_bytes == 1875000


# ---------------------------------------------------------------------------
# TransportPlan / TransportController plumbing
# ---------------------------------------------------------------------------

def _row(rtt_ms, bw, rtx=0):
    # digest "lk" row layout (ps/linkstate.py): [rtt_ms, bw_mbps,
    # rtt_var, bw_var, goodput, rtx, give_ups, n_small, n_big]
    return [rtt_ms, bw, 0.0, 0.0, bw / 8.0, rtx, 0, 4, 8]


class _FakeEstimator:
    def __init__(self, rows):
        self.rows = rows

    def digest(self):
        return {"lk": self.rows}


def _controller(est, flightrec=None, out_dir="", board_fn=None):
    return ctrl.TransportController(
        Config(), tier="global", node_fn=lambda: 9, estimator=est,
        board_fn=board_fn, flightrec=flightrec, out_dir=out_dir)


def test_plan_wire_tag_resolves_policy_per_chunk():
    plan = ctrl.TransportPlan(3, {10: "mpq", 12: "fp16"}, 0, {},
                              size_lower_bound=200000)
    assert plan.wire_tag(10, "", 300000) == "2bit"   # bulk chunk
    assert plan.wire_tag(10, "", 1000) == "fp16"     # small chunk
    assert plan.wire_tag(12, "", 300000) == "fp16"
    # no decision for this peer: static default rides
    assert plan.wire_tag(99, "2bit", 5) == "2bit"
    assert plan.wire_tag(99, "", 5) == ""


def test_wan_tag_thinnest_class_governs():
    est = _FakeEstimator({"8": _row(50.0, 200.0)})
    c = _controller(est)
    c.plan(1)
    assert c.wan_tag(300000) == "fp16"               # all fat
    est.rows = {"8": _row(50.0, 200.0), "10": _row(150.0, 10.0)}
    for r in (2, 3):
        c.plan(r)
    assert c.wan_tag(300000) == "2bit"               # thin peer governs
    assert c.wan_tag(1000) == "fp16"                 # mpq size rule
    # no decisions at all -> None (static precedence continues)
    c2 = _controller(_FakeEstimator({}))
    c2.plan(1)
    assert c2.wan_tag(300000) is None


def test_plan_is_idempotent_per_round():
    est = _FakeEstimator({"8": _row(50.0, 20.0)})
    c = _controller(est)
    p = c.plan(4)
    est.rows = {"8": _row(50.0, 200.0)}
    assert c.plan(4) is p            # same round: cached
    assert c.plan(3) is p            # stale round: cached
    assert c.plan(5) is not p        # new round: recomputed


def test_degraded_board_input_feeds_decision():
    est = _FakeEstimator({"8": _row(50.0, 200.0)})
    board = {"links": {"9>8": {"degraded": True},
                       "11>8": {"degraded": True}}}
    c = _controller(est, board_fn=lambda: board)
    p = c.plan(1)
    # healthy bandwidth, but the board latched 9>8: thin NOW
    assert p.codecs[8] == ctrl.THIN_POLICY
    assert p.reasons[8] == "degraded"


def test_replay_from_flightrec_dump(tmp_path):
    """Acceptance bar: every decision is reconstructable from a flight-
    recorder dump — each transport_plan record carries inputs + embedded
    pre-state, so a dump replays standalone."""
    rec = FlightRecorder(lambda: "n9", size=256, out_dir=str(tmp_path))
    est = _FakeEstimator({"8": _row(50.0, 200.0)})
    c = _controller(est, flightrec=rec, out_dir=str(tmp_path))
    c.plan(1)
    est.rows = {"8": _row(50.0, 10.0, rtx=0)}        # squeeze
    for r in (2, 3, 4):
        c.plan(r)
    path = rec.dump("test: controller replay")
    events = json.loads(open(path).read())["events"]
    plans = [e for e in events if e["kind"] == "transport_plan"]
    assert len(plans) == 4
    assert any(e["changed"] and e["codec"] == ctrl.THIN_POLICY
               for e in plans)
    for e in plans:
        assert ctrl.replay_record(e, c.knobs) == {
            "codec": e["codec"], "changed": e["changed"],
            "reason": e["reason"]}, e
    # the squeeze decision also hit the telemetry funnel
    # (transport.codec events are counted by the registry)


def test_plan_export_and_geomx_top_render(tmp_path):
    est = _FakeEstimator({"8": _row(50.0, 10.0)})
    c = _controller(est, out_dir=str(tmp_path))
    c.plan(1)
    plans = geomx_top.load_plans(str(tmp_path))
    assert ("global", 9) in plans
    doc = plans[("global", 9)]
    assert doc["links"]["8"]["codec"] == ctrl.THIN_POLICY
    assert doc["slice_bytes"] == auto_slice_bytes(50.0, 10.0)
    board = {"tier": "global", "node": "g8", "max_round": 1,
             "links": {"9>8": {"rtt_ms": 50.0, "bw_mbps": 10.0,
                               "rtx": 0, "give_ups": 0}}}
    text = geomx_top.render_board(board, plans=plans)
    assert "mpq[thin_bw]" in text
    assert "transport plan slice budgets" in text
    # a local-tier plan for the same numeric id must NOT leak onto the
    # global board's rows
    lplans = {("local", 9): doc}
    assert "mpq[" not in geomx_top.render_board(board, plans=lplans)


# ---------------------------------------------------------------------------
# controller off: today's behavior, bit-for-bit
# ---------------------------------------------------------------------------

def test_controller_defaults_off():
    c = Config()
    assert c.transport_controller is False


def test_pick_pair_rng_sequence_unchanged_when_bias_off():
    """GEOMX_TRANSPORT_CONTROLLER=0 must reproduce the PR-12 overlay
    matchmaking bit-for-bit: with no degraded set, _pick_pair consumes
    the RNG in exactly the legacy order (random() gate, then sample or
    shuffle+argmax)."""
    sched = TSScheduler(object(), num_workers=4, greed_rate=0.9)
    ref = random.Random(0x75)
    for _ in range(200):
        pend = {9, 11, 13, 15}
        # replicate list(pend)'s iteration order: the scheduler's RNG
        # draws (sample / shuffle) depend on it
        ids = list(pend)
        pairs = [(s, r) for s in ids for r in ids if s != r]
        got = sched._pick_pair(pend)
        if ref.random() >= sched.greed:
            exp = tuple(ref.sample(ids, 2))
        else:
            ref.shuffle(pairs)
            best, best_t = pairs[0], -1.0
            for s, r in pairs:
                t = sched.A.get((s, r), 0.0)
                if t > best_t:
                    best, best_t = (s, r), t
            exp = best
        assert tuple(got) == exp


def test_pick_pair_avoids_degraded_until_all_degraded():
    sched = TSScheduler(object(), num_workers=4, greed_rate=1.0)
    ids = [9, 11, 13]
    bad = frozenset({(9, 11), (11, 9), (9, 13), (13, 9)})
    for _ in range(50):
        rerouted = []
        s, r = sched._pick_pair(set(ids), bad, rerouted)
        assert (s, r) not in bad
        assert rerouted  # the filter engaged and was logged
    # every pair degraded: fall back to a plain pick (a stalled overlay
    # is worse than a slow hop)
    all_bad = frozenset((s, r) for s in ids for r in ids if s != r)
    s, r = sched._pick_pair(set(ids), all_bad, [])
    assert s != r and s in ids and r in ids


# ---------------------------------------------------------------------------
# e2e: mid-run squeeze absorbed, plan flips, every decision replayable
# ---------------------------------------------------------------------------

def test_e2e_squeeze_flips_plan_without_round_abort(tmp_path):
    """2-party HiPS on the wan2 plan (100 Mbps links: dead zone, so the
    controller starts with NO codec override) with the transport
    controller ON. A mid-run squeeze of 9->8 to 10 Mbps must be
    absorbed without a round abort; the board's link_degraded fires AND
    the party server's exported TransportPlan assigns the thin policy
    to peer 8 within 3 rounds of the detection; every logged decision
    replays from the flight recorder."""
    telemetry.enable(True)
    health_dir = str(tmp_path / "health")
    sim = InProcessHiPS(
        num_parties=2, workers_per_party=1,
        extra_cfg=dict(
            shape_plan="@" + SHAPE_PLAN,
            resend=True, resend_timeout_ms=2000, resend_deadline_s=120.0,
            heartbeat_interval_s=0.2, heartbeat_timeout_s=60,
            health=True, health_dir=health_dir,
            transport_controller=True,
        )).start(sync_global=True)
    try:
        sim.master.set_optimizer(SGD(learning_rate=1.0))
        big = np.zeros(65_536, np.float32)            # 256 KB bw probe

        def init_on(kv):
            kv.init(1, big)
            kv.wait()

        _parallel([lambda kv=kv: init_on(kv)
                   for kv in sim.workers + [sim.master]])

        def step(kv):
            kv.push_pull(1, np.ones(65_536, np.float32),
                         np.zeros(65_536, np.float32))
            kv.wait()

        def wan_plan():
            plans = geomx_top.load_plans(health_dir)
            return plans.get(("global", 9))

        for _ in range(5):  # healthy baseline rounds
            _parallel([lambda kv=kv: step(kv) for kv in sim.workers])
        baseline = wan_plan()
        assert baseline is not None, "controller exported no plan"
        # 100 Mbps sits in the dead zone between thin and fat: a
        # measured-but-unclassified link takes the fp16 floor
        assert baseline["links"].get("8", {}).get("codec", "") == \
            ctrl.FAT_POLICY

        gsrv = sim.servers[0]
        assert gsrv.is_global_server
        gsrv.po_global.van._shaper.plan.links.insert(0, ShapeLink(
            src=9, dst=8, tier="global", rtt_ms=50.0, bw_mbps=10.0))

        def board_degraded():
            got = sim.workers[0].health()
            for b in got["global"]:
                if b.get("tier") != "global":
                    continue
                lk = b.get("links", {}).get("9>8")
                if lk and lk.get("degraded"):
                    return True
            return False

        detect_round = plan_round = None
        for r in range(12):  # squeeze rounds: no abort tolerated
            _parallel([lambda kv=kv: step(kv) for kv in sim.workers])
            time.sleep(0.45)  # heartbeat cadence: digests land
            if detect_round is None and board_degraded():
                detect_round = r
            p = wan_plan()
            if plan_round is None and p is not None \
                    and p["links"].get("8", {}).get("codec") == \
                    ctrl.THIN_POLICY:
                plan_round = r
            if detect_round is not None and plan_round is not None:
                break
        assert detect_round is not None, "link_degraded never fired"
        assert plan_round is not None, "TransportPlan never flipped"
        assert plan_round <= detect_round + 3, (
            f"plan lagged detection: detected r{detect_round}, "
            f"flipped r{plan_round}")

        # every logged decision replays from the party server's ring
        party = next(s for s in sim.servers
                     if getattr(s, "_transport", None) is not None
                     and s.po_global.van.my_id == 9)
        recs = [e for e in party.po_global.van.flightrec.snapshot()
                if e["kind"] == "transport_plan"]
        assert recs, "no transport_plan flight-recorder records"
        assert any(e["changed"] and e["codec"] == ctrl.THIN_POLICY
                   for e in recs)
        for e in recs:
            assert ctrl.replay_record(e, party._transport.knobs) == {
                "codec": e["codec"], "changed": e["changed"],
                "reason": e["reason"]}
        # the codec flip hit the telemetry funnel
        counts = telemetry.snapshot()["counters"]
        assert counts.get("event.transport.codec", 0) >= 1
    finally:
        sim.stop()
