"""FaultPlan parsing + deterministic injection primitives.

Each primitive (drop / dup / delay / reorder / partition / crash) is
exercised against a stub van with a scripted message stream, twice, and
the two injectors' ``decision_log`` audit trails must match exactly:
same seed + same plan + same traffic => the identical schedule. That is
the contract the chaos matrix (scripts/run_chaos_matrix.sh) and the
crash-resume acceptance test lean on.
"""

import json
import threading
import time
import types

import pytest

from geomx_tpu import config as cfg_mod
from geomx_tpu.config import Config
from geomx_tpu.ps import faults
from geomx_tpu.ps.faults import FaultPlan, FaultRule

pytestmark = pytest.mark.chaos


# ---------------------------------------------------------------------------
# parsing / validation


def test_rule_rejects_unknown_type():
    with pytest.raises(ValueError, match="type must be one of"):
        FaultRule.from_dict({"type": "scramble"})


def test_rule_rejects_unknown_fields():
    with pytest.raises(ValueError, match="unknown fault rule fields"):
        FaultRule.from_dict({"type": "drop", "probability": 0.5})


def test_partition_requires_between_pair():
    with pytest.raises(ValueError, match="between"):
        FaultRule.from_dict({"type": "partition"})
    with pytest.raises(ValueError, match="between"):
        FaultRule.from_dict({"type": "partition", "between": [9]})


def test_reorder_requires_window():
    with pytest.raises(ValueError, match="window >= 2"):
        FaultRule.from_dict({"type": "reorder", "window": 1})


def test_crash_requires_valid_side():
    with pytest.raises(ValueError, match="'recv' or 'send'"):
        FaultRule.from_dict({"type": "crash", "at": 1, "on": "wire"})


def test_parse_dict_with_embedded_seed():
    plan = FaultPlan.parse(
        '{"seed": 42, "rules": [{"type": "drop", "p": 0.5}]}', seed=7)
    assert plan.seed == 42            # embedded seed wins over PS_SEED
    assert len(plan.rules) == 1
    assert plan.rules[0].kind == "drop"


def test_parse_bare_list():
    plan = FaultPlan.parse('[{"type": "dup", "p": 0.1}]', seed=7)
    assert plan.seed == 7
    assert plan.rules[0].kind == "dup"


def test_parse_at_file(tmp_path):
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(
        {"seed": 3, "rules": [{"type": "delay", "delay_s": 0.5}]}))
    plan = FaultPlan.parse("@" + str(p))
    assert plan.seed == 3
    assert plan.rules[0].delay_s == 0.5


def test_plan_from_config_seed_precedence():
    # no plan -> None
    assert faults.plan_from_config(Config()) is None
    # PS_SEED flows into a seedless plan
    plan = faults.plan_from_config(
        Config(fault_plan='[{"type": "drop", "p": 0.3}]', ps_seed=11))
    assert plan.seed == 11
    # unseeded everywhere -> None seed (wall-clock entropy)
    plan = faults.plan_from_config(
        Config(fault_plan='[{"type": "drop", "p": 0.3}]'))
    assert plan.seed is None


def test_env_round_trip(monkeypatch):
    monkeypatch.setenv("PS_FAULT_PLAN",
                       '[{"type": "drop", "p": 0.25, "dst": 9}]')
    monkeypatch.setenv("PS_SEED", "5")
    cfg = cfg_mod.load()
    plan = faults.plan_from_config(cfg)
    assert plan.seed == 5
    assert plan.rules[0].p == 0.25
    assert plan.rules[0].dst == 9


def test_van_seed_stable_and_distinct():
    cfg = Config(ps_seed=7)
    a = faults.van_seed(cfg, my_role=1, is_global=False)
    assert a == faults.van_seed(cfg, my_role=1, is_global=False)
    assert a != faults.van_seed(cfg, my_role=2, is_global=False)
    assert a != faults.van_seed(cfg, my_role=1, is_global=True)
    assert faults.van_seed(Config(), my_role=1, is_global=False) is None


# ---------------------------------------------------------------------------
# injection primitives against a stub van


class StubVan:
    """Just enough van surface for FaultInjector: identity, a stopped
    event, and a _process sink recording re-injected frames."""

    def __init__(self, my_id=9, is_global=False):
        self.my_id = my_id
        self.is_global = is_global
        self.stopped = threading.Event()
        self.delivered = []
        self.crashed = []

    def _process(self, msg):
        self.delivered.append(msg)

    def round_args(self, trace_round):
        return {"node": self.my_id, "round": trace_round}

    def _crash_from_fault(self, reason):
        self.crashed.append(reason)
        self.stopped.set()


def msg(sender=8, control=False, tag=None):
    m = types.SimpleNamespace()
    m.meta = types.SimpleNamespace(sender=sender, trace_round=-1)
    m.is_control = control
    m.tag = tag
    return m


def run_stream(plan_json, n=40, seed=123, sender=8, my_id=9):
    """Feed n identical frames through a fresh injector; return
    (injector, [on_inbound results], van)."""
    plan = FaultPlan.parse(plan_json, seed=seed)
    van = StubVan(my_id=my_id)
    inj = plan.bind(van)
    inj.arm()
    results = [inj.on_inbound(msg(sender=sender, tag=i)) for i in range(n)]
    return inj, results, van


def test_drop_deterministic_and_partial():
    plan = '[{"type": "drop", "p": 0.5}]'
    inj1, res1, _ = run_stream(plan)
    inj2, res2, _ = run_stream(plan)
    assert res1 == res2
    assert inj1.decision_log == inj2.decision_log
    assert True in res1 and False in res1   # p=0.5 actually drops some
    # a different seed gives a different schedule
    _, res3, _ = run_stream(plan, seed=124)
    assert res1 != res3


def test_drop_spares_control_frames_by_default():
    plan = FaultPlan.parse('[{"type": "drop", "p": 1.0}]', seed=1)
    van = StubVan()
    inj = plan.bind(van)
    assert inj.on_inbound(msg(control=True)) is True
    assert inj.on_inbound(msg(control=False)) is False
    # opt-in faults the control plane too
    plan = FaultPlan.parse('[{"type": "drop", "p": 1.0, "control": true}]',
                           seed=1)
    inj = plan.bind(StubVan())
    assert inj.on_inbound(msg(control=True)) is False


def test_drop_scoping_by_src_dst():
    plan = FaultPlan.parse('[{"type": "drop", "p": 1.0, "src": 8, '
                           '"dst": [9, 11]}]', seed=1)
    inj = plan.bind(StubVan(my_id=9))
    assert inj.on_inbound(msg(sender=8)) is False    # matches
    assert inj.on_inbound(msg(sender=10)) is True    # wrong src
    inj = plan.bind(StubVan(my_id=13))
    assert inj.on_inbound(msg(sender=8)) is True     # wrong dst


def test_dup_redelivers_through_dispatch():
    plan = '[{"type": "dup", "p": 0.5}]'
    inj1, res1, van1 = run_stream(plan)
    inj2, res2, van2 = run_stream(plan)
    assert inj1.decision_log == inj2.decision_log
    assert all(res1)                   # dup never withholds the original
    n_dup = sum(1 for e in inj1.decision_log if e[5] == "dup")
    assert n_dup > 0
    deadline = time.monotonic() + 5
    while len(van1.delivered) < n_dup and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(van1.delivered) == n_dup   # each dup re-injected once


def test_delay_holds_then_redelivers():
    plan = '[{"type": "delay", "delay_s": 0.05, "jitter_s": 0.02}]'
    inj1, res1, van1 = run_stream(plan, n=10)
    inj2, res2, van2 = run_stream(plan, n=10)
    assert inj1.decision_log == inj2.decision_log   # incl. delay values
    assert not any(res1)               # all held for later delivery
    deadline = time.monotonic() + 5
    while len(van1.delivered) < 10 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert [m.tag for m in sorted(van1.delivered, key=lambda m: m.tag)] \
        == list(range(10))             # nothing lost


def test_reorder_flushes_permuted_window():
    plan = '[{"type": "reorder", "window": 4}]'
    inj1, res1, van1 = run_stream(plan, n=8)
    inj2, res2, van2 = run_stream(plan, n=8)
    assert inj1.decision_log == inj2.decision_log
    assert not any(res1)               # held or flushed via _process
    # two full windows flushed synchronously, all 8 frames delivered
    assert sorted(m.tag for m in van1.delivered) == list(range(8))
    assert [m.tag for m in van1.delivered] == \
        [m.tag for m in van2.delivered]
    # at least one window actually permuted (seed chosen accordingly)
    assert [m.tag for m in van1.delivered] != list(range(8))


def test_partition_window_is_time_scoped():
    plan = FaultPlan.parse(
        '[{"type": "partition", "between": [8, 9], "start_s": 0.0, '
        '"duration_s": 0.2}]', seed=1)
    van = StubVan(my_id=9)
    inj = plan.bind(van)
    inj.arm()
    assert inj.on_inbound(msg(sender=8)) is False   # inside the window
    assert inj.on_inbound(msg(sender=10)) is True   # unrelated link
    time.sleep(0.25)
    assert inj.on_inbound(msg(sender=8)) is True    # window closed


def test_crash_on_nth_recv():
    plan = FaultPlan.parse(
        '[{"type": "crash", "node": 9, "at": 3, "on": "recv"}]', seed=1)
    van = StubVan(my_id=9)
    inj = plan.bind(van)
    assert inj.on_inbound(msg()) is True
    assert inj.on_inbound(msg()) is True
    assert inj.on_inbound(msg()) is False           # third frame kills it
    assert van.stopped.wait(5)
    assert van.crashed and "crash rule #0" in van.crashed[0]
    assert inj.on_inbound(msg()) is False           # dead vans stay dead


def test_crash_on_send_side():
    plan = FaultPlan.parse(
        '[{"type": "crash", "node": 9, "at": 2, "on": "send"}]', seed=1)
    van = StubVan(my_id=9)
    inj = plan.bind(van)
    assert inj.on_send(10, msg(sender=9)) is True
    assert inj.on_send(10, msg(sender=9, control=True)) is True  # exempt
    assert inj.on_send(10, msg(sender=9)) is False
    assert van.stopped.wait(5)


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
