"""ShapePlan parsing + deterministic link shaping primitives.

Mirrors test_faults.py: every shaping primitive (fixed delay, token
bucket queueing, jitter, shared access pipes, control exemption) runs
against a stub van — twice where determinism is the contract — and the
two shapers' ``decision_log`` audit trails must match exactly: same
plan + same seed + same traffic => the identical delivery schedule.
That is the acceptance bar the shaped captures (PERF.md) and the chaos
matrix's shaped cases lean on, and it is what makes a shaped run a
reproducible experiment instead of a noisy one.
"""

import json
import threading
import time
import types

import numpy as np
import pytest

from geomx_tpu.config import Config
from geomx_tpu.kvstore import frontier, sharding
from geomx_tpu.ps import shaping
from geomx_tpu.ps.shaping import LinkShaper, ShapeLink, ShapePlan
from geomx_tpu.ps.van import Van

pytestmark = pytest.mark.chaos


# ---------------------------------------------------------------------------
# parsing / validation


def test_link_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown keys"):
        ShapeLink.from_dict({"rtt_ms": 10, "bandwidth": 100})


def test_link_rejects_bad_tier():
    with pytest.raises(ValueError, match="bad tier"):
        ShapeLink.from_dict({"tier": "wan"})


def test_link_rejects_negative_values():
    with pytest.raises(ValueError, match=">= 0"):
        ShapeLink.from_dict({"rtt_ms": -1})
    with pytest.raises(ValueError, match=">= 0"):
        ShapeLink.from_dict({"bw_mbps": -5})


def test_parse_dict_with_embedded_seed():
    plan = ShapePlan.parse(
        '{"seed": 42, "links": [{"rtt_ms": 10}]}', seed=7)
    assert plan.seed == 42            # embedded seed wins
    assert len(plan.links) == 1 and plan.default is None


def test_parse_bare_list_and_default():
    plan = ShapePlan.parse('[{"rtt_ms": 10}]', seed=7)
    assert plan.seed == 7 and plan.default is None
    plan = ShapePlan.parse(
        '{"default": {"rtt_ms": 50, "bw_mbps": 100}, "links": []}')
    assert plan.default.rtt_ms == 50


def test_parse_at_file(tmp_path):
    p = tmp_path / "shape.json"
    p.write_text(json.dumps({"seed": 3, "links": [{"bw_mbps": 20}]}))
    plan = ShapePlan.parse("@" + str(p))
    assert plan.seed == 3
    assert plan.links[0].bw_mbps == 20


def test_plan_from_config_seed_precedence():
    assert shaping.plan_from_config(Config()) is None
    # GEOMX_SHAPE_SEED beats PS_SEED
    plan = shaping.plan_from_config(
        Config(shape_plan='[{"rtt_ms": 1}]', shape_seed=5, ps_seed=11))
    assert plan.seed == 5
    # PS_SEED is the fallback
    plan = shaping.plan_from_config(
        Config(shape_plan='[{"rtt_ms": 1}]', ps_seed=11))
    assert plan.seed == 11
    # plan-embedded seed beats both
    plan = shaping.plan_from_config(
        Config(shape_plan='{"seed": 2, "links": [{"rtt_ms": 1}]}',
               shape_seed=5, ps_seed=11))
    assert plan.seed == 2


def test_link_for_first_match_wins_and_tier_scoping():
    plan = ShapePlan.parse(json.dumps({"links": [
        {"src": 9, "dst": 8, "rtt_ms": 150},
        {"dst": 8, "rtt_ms": 50},
        {"tier": "local", "rtt_ms": 1},
    ], "default": {"rtt_ms": 99}}))
    assert plan.link_for(9, 8, True).rtt_ms == 150    # first match wins
    assert plan.link_for(11, 8, True).rtt_ms == 50
    assert plan.link_for(11, 9, False).rtt_ms == 1    # local-tier rule
    assert plan.link_for(11, 9, True).rtt_ms == 99    # default
    plan = ShapePlan.parse('[{"tier": "local", "rtt_ms": 1}]')
    assert plan.link_for(11, 9, True) is None         # unmatched: unshaped


def test_worst_link_picks_highest_bdp():
    plan = ShapePlan.parse(json.dumps({"links": [
        {"rtt_ms": 10, "bw_mbps": 1000},   # BDP 1.25 MB
        {"rtt_ms": 150, "bw_mbps": 20},    # BDP 375 KB
        {"rtt_ms": 200, "bw_mbps": 100},   # BDP 2.5 MB <- worst
    ]}))
    assert plan.worst_link(is_global=True) == (200, 100)
    assert ShapePlan.parse("[]").worst_link() is None


# ---------------------------------------------------------------------------
# shaping primitives against a stub van


class StubVan:
    """Just enough van surface for LinkShaper + deliver_later: identity,
    a stopped event, and a _process sink recording held frames as they
    re-enter dispatch."""

    def __init__(self, my_id=8, is_global=True):
        self.my_id = my_id
        self.is_global = is_global
        self.stopped = threading.Event()
        self.delivered = []

    def _process(self, msg):
        self.delivered.append(msg)

    def round_args(self, trace_round):
        return {"node": self.my_id, "round": trace_round}


def msg(sender=9, nbytes=0, control=False):
    m = types.SimpleNamespace()
    m.meta = types.SimpleNamespace(sender=sender, trace_round=-1)
    m.is_control = control
    m.data = [b"\0" * nbytes] if nbytes else []
    return m


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_fixed_delay_holds_then_redelivers():
    plan = ShapePlan.parse('[{"rtt_ms": 20}]', seed=1)
    van = StubVan()
    sh = plan.bind(van)
    m = msg(nbytes=10)
    assert sh.on_inbound(m) is False      # held for rtt/2
    deadline = time.monotonic() + 5
    while not van.delivered and time.monotonic() < deadline:
        time.sleep(0.005)
    assert van.delivered == [m]
    (src, dst, seq, nb, delay_ms) = sh.decision_log[0]
    assert (src, dst, seq, nb) == (9, 8, 1, 10)
    assert delay_ms == pytest.approx(10.0)


def test_zero_delay_link_delivers_inline():
    # a 0-rtt infinite-bw rule matches but never holds the frame
    plan = ShapePlan.parse('[{"rtt_ms": 0, "bw_mbps": 0}]', seed=1)
    sh = plan.bind(StubVan())
    assert sh.on_inbound(msg(nbytes=100)) is True
    assert len(sh.decision_log) == 1


def test_control_frames_exempt_unless_opted_in():
    plan = ShapePlan.parse('[{"rtt_ms": 100}]', seed=1)
    sh = plan.bind(StubVan())
    assert sh.on_inbound(msg(control=True)) is True
    assert sh.decision_log == []          # exempt frames leave no trace
    plan = ShapePlan.parse('[{"rtt_ms": 100, "control": true}]', seed=1)
    sh = plan.bind(StubVan())
    assert sh.on_inbound(msg(control=True)) is False


def test_token_bucket_queues_back_to_back_frames():
    # 1 MB at 8 Mbps = 1.0 s serialization per frame; with a fake clock
    # the horizons stack exactly: 1 s, 2 s, 3 s (+ rtt/2 each)
    plan = ShapePlan.parse('[{"src": 9, "rtt_ms": 20, "bw_mbps": 8}]',
                           seed=1)
    van = StubVan()
    sh = LinkShaper(plan, van, clock=FakeClock())
    for _ in range(3):
        sh.on_inbound(msg(sender=9, nbytes=1_000_000))
    delays = [e[4] for e in sh.decision_log]
    assert delays == pytest.approx([1010.0, 2010.0, 3010.0])
    # an unmatched src is unshaped: delivered inline, no bucket, no log
    assert sh.on_inbound(msg(sender=11, nbytes=1_000_000)) is True
    assert len(sh.decision_log) == 3


def test_per_link_fifo_under_jitter():
    # folding jitter into the horizon keeps per-link delivery FIFO:
    # absolute delivery times (clock fixed => delay order) never invert
    plan = ShapePlan.parse(
        '[{"rtt_ms": 10, "bw_mbps": 100, "jitter_ms": 5}]', seed=9)
    sh = LinkShaper(plan, StubVan(), clock=FakeClock())
    for _ in range(20):
        sh.on_inbound(msg(nbytes=10_000))
    delays = [e[4] for e in sh.decision_log]
    assert delays == sorted(delays)
    assert len(set(delays)) == len(delays)   # jitter actually spreads


def test_schedule_deterministic_same_seed_differs_across_seeds():
    plan_json = ('[{"rtt_ms": 30, "bw_mbps": 50, "jitter_ms": 4}]')

    def run(seed):
        plan = ShapePlan.parse(plan_json, seed=seed)
        sh = LinkShaper(plan, StubVan(), clock=FakeClock())
        for i in range(30):
            sh.on_inbound(msg(sender=9 + 2 * (i % 3), nbytes=50_000 + i))
        return sh.decision_log

    assert run(7) == run(7)               # identical delivery schedule
    assert run(7) != run(8)               # seed actually reaches jitter


def test_shared_ingress_pipe_contends_across_senders():
    # private per-pair buckets would give both senders 1 s each; the
    # shared rule makes the second sender queue behind the first
    plan = ShapePlan.parse(
        '{"links": [{"dst": 8, "shared": true, "rtt_ms": 0,'
        ' "bw_mbps": 8}]}', seed=1)
    sh = LinkShaper(plan, StubVan(my_id=8), clock=FakeClock())
    sh.on_inbound(msg(sender=9, nbytes=1_000_000))
    sh.on_inbound(msg(sender=11, nbytes=1_000_000))
    delays = [e[4] for e in sh.decision_log]
    assert delays == pytest.approx([1000.0, 2000.0])


def test_shared_egress_pipe_contends_across_shapers():
    # frames fanning out from one src to two receivers hit two different
    # receiver-side shapers; the process-global registry still
    # serializes them on the src's one egress pipe
    shaping.reset_shared_buckets()
    try:
        plan = ShapePlan.parse(
            '{"links": [{"src": 8, "shared": true, "rtt_ms": 0,'
            ' "bw_mbps": 40}]}', seed=1)
        sh_a = plan.bind(StubVan(my_id=9))
        sh_b = plan.bind(StubVan(my_id=11))
        sh_a.on_inbound(msg(sender=8, nbytes=1_000_000))   # 0.2 s ser
        sh_b.on_inbound(msg(sender=8, nbytes=1_000_000))
        d_a = sh_a.decision_log[0][4]
        d_b = sh_b.decision_log[0][4]
        assert d_a == pytest.approx(200.0, rel=0.05)
        assert d_b == pytest.approx(400.0, rel=0.05)       # queued behind a
    finally:
        shaping.reset_shared_buckets()


def test_fake_clock_shared_buckets_stay_instance_private():
    # determinism tests rely on fake-clock shapers NOT touching the
    # process-global registry (wall-clock horizons would wedge them)
    shaping.reset_shared_buckets()
    plan = ShapePlan.parse(
        '{"links": [{"dst": 8, "shared": true, "bw_mbps": 8}]}', seed=1)
    sh = LinkShaper(plan, StubVan(my_id=8), clock=FakeClock())
    sh.on_inbound(msg(sender=9, nbytes=1_000_000))
    assert shaping._shared_horizons == {}


# ---------------------------------------------------------------------------
# composition with the fault plan (Van._inbound_gate ordering)


def _gate_stub(shaper=None, injector=None):
    """A bare object carrying exactly the attributes _inbound_gate
    reads, so the REAL gate method runs against scripted frames."""
    stub = types.SimpleNamespace()
    stub._faults = injector
    stub._shaper = shaper
    stub.drop_rate = 0.0
    stub._rng = None
    stub.verbose = False
    stub.num_data_recv = 0
    stub._stats_lock = threading.Lock()
    return stub


def test_gate_runs_faults_before_shaping():
    from geomx_tpu.ps.faults import FaultPlan

    fplan = FaultPlan.parse('[{"type": "drop", "p": 1.0}]', seed=1)
    splan = ShapePlan.parse('[{"rtt_ms": 100}]', seed=1)
    van = StubVan()
    inj = fplan.bind(van)
    sh = LinkShaper(splan, van, clock=FakeClock())
    stub = _gate_stub(shaper=sh, injector=inj)
    assert Van._inbound_gate(stub, msg(nbytes=10)) is False
    # the dropped frame never reached the shaper — no bucket occupancy,
    # no decision, and it was never counted as received either
    assert sh.decision_log == []
    assert stub.num_data_recv == 0


def test_gate_counts_frame_before_shaping_hold():
    splan = ShapePlan.parse('[{"rtt_ms": 100}]', seed=1)
    van = StubVan()
    sh = LinkShaper(splan, van, clock=FakeClock())
    stub = _gate_stub(shaper=sh)
    assert Van._inbound_gate(stub, msg(nbytes=10)) is False  # held
    # a held frame is on the (emulated) wire: crash-at-message-N fault
    # points must land identically shaped or not
    assert stub.num_data_recv == 1
    assert len(sh.decision_log) == 1


# ---------------------------------------------------------------------------
# slice sizing from the topology (frontier + sharding plumbing)


def test_auto_slice_bytes_tracks_bdp():
    assert frontier.auto_slice_bytes(0, 100) == 0        # unshaped
    assert frontier.auto_slice_bytes(50, 0) == 4 << 20   # latency-only
    # 50 ms * 100 Mbps = 625 KB BDP
    assert frontier.auto_slice_bytes(50, 100) == 625_000
    assert frontier.auto_slice_bytes(1, 1) == 65536      # clamps to min


def test_slice_bytes_from_shape_uses_worst_global_link():
    cfg = Config(shape_plan=json.dumps({"links": [
        {"rtt_ms": 10, "bw_mbps": 100, "tier": "global"},
        {"rtt_ms": 200, "bw_mbps": 100, "tier": "global"},
        {"rtt_ms": 500, "bw_mbps": 100, "tier": "local"},
    ]}))
    assert frontier.slice_bytes_from_shape(cfg) == \
        frontier.auto_slice_bytes(200, 100)
    assert frontier.slice_bytes_from_shape(Config()) == 0


def test_split_slices_refines_without_moving_boundaries():
    shards = sharding.assign(0, 1000, 2, bigarray_bound=100)
    fine = sharding.split_slices(shards, 128)
    assert sharding.split_slices(shards, 0) == shards    # 0 = no refine
    assert sum(s.length for s in fine) == 1000
    assert all(s.length <= 128 for s in fine)
    # placement and outer boundaries untouched: a peer addressing the
    # coarse ranges overlaps a contiguous run of the fine ones
    for coarse in shards:
        sub = [s for s in fine if s.server_rank == coarse.server_rank
               and coarse.offset <= s.offset < coarse.offset + coarse.length]
        assert sub[0].offset == coarse.offset
        assert sub[-1].offset + sub[-1].length == \
            coarse.offset + coarse.length
