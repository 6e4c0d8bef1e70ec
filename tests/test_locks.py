"""geomx-racecheck runtime sanitizer (geomx_tpu/ps/locks.py) tests.

Harness half: real two-thread seeded inversions, blocking-call probes,
Condition.wait semantics and the Eraser-style @guarded_by lockset, all
against a fresh process-global witness per test.

Off-path half: with the sanitizer disabled the factories must hand back
the *raw* threading primitives (same class, not a wrapper), and an
acquire/release loop through a factory-built lock must cost within 5%
of a hand-built ``threading.Lock`` (the ISSUE acceptance bar).
"""

import logging
import threading
import time
import timeit

import pytest

from geomx_tpu import config as cfg_mod
from geomx_tpu.ps import locks

assert locks.MARKER  # the grep target scripts/run_chaos_matrix.sh fails on


@pytest.fixture(autouse=True)
def _restore_sanitizer_state():
    """Every test flips the process-global witness/enable flag; restore
    the environment-derived default afterwards so no state leaks into
    the rest of the tier-1 run."""
    yield
    locks.reset_for_tests(on=cfg_mod.env_bool("GEOMX_LOCK_SANITIZER"))


def _run_in_thread(fn):
    errs = []

    def runner():
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — surfaced via assert
            errs.append(e)

    t = threading.Thread(target=runner)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), "harness thread wedged"
    assert not errs, errs
    return t


# ---------------------------------------------------------------------------
# acquisition-order graph
# ---------------------------------------------------------------------------

def test_seeded_inversion_latches_exactly_once(caplog):
    w = locks.reset_for_tests(on=True)
    a = locks.make_lock("inv.A")
    b = locks.make_lock("inv.B")

    def forward():
        with a:
            with b:
                pass

    def inverted():
        with b:
            with a:
                pass

    with caplog.at_level(logging.ERROR, logger="geomx.locks"):
        _run_in_thread(forward)
        _run_in_thread(inverted)
        # re-seeding the same pair must NOT re-fire: latched per pair
        _run_in_thread(inverted)

    assert len(w.violations) == 1
    desc = w.violations[0]
    assert "lock-order inversion" in desc
    assert "inv.A" in desc and "inv.B" in desc
    # both acquisition stacks are named, one per direction
    assert "this thread:" in desc and "seen before:" in desc
    assert desc.count("test_locks.py") >= 2
    assert any(locks.MARKER in r.getMessage() for r in caplog.records)


def test_lock_ordered_control_is_clean():
    w = locks.reset_for_tests(on=True)
    a = locks.make_lock("ctl.A")
    b = locks.make_lock("ctl.B")

    def worker():
        for _ in range(200):
            with a:
                with b:
                    pass

    ts = [threading.Thread(target=worker) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
        assert not t.is_alive()

    assert w.violations == []
    assert w.report() == []


def test_three_lock_cycle_is_flagged():
    w = locks.reset_for_tests(on=True)
    a = locks.make_lock("cyc.A")
    b = locks.make_lock("cyc.B")
    c = locks.make_lock("cyc.C")

    def edge(first, second):
        def body():
            with first:
                with second:
                    pass
        return body

    _run_in_thread(edge(a, b))
    _run_in_thread(edge(b, c))
    assert w.violations == []  # A->B->C alone is a fine total order
    _run_in_thread(edge(c, a))
    assert len(w.violations) == 1
    assert "lock-order cycle" in w.violations[0]
    for name in ("cyc.A", "cyc.B", "cyc.C"):
        assert name in w.violations[0]


def test_rlock_reentrancy_is_silent():
    w = locks.reset_for_tests(on=True)
    r = locks.make_rlock("re.R")
    with r:
        with r:
            assert r.held_by_me()
    assert not r.held_by_me()
    assert w.violations == []


# ---------------------------------------------------------------------------
# blocking-call probes
# ---------------------------------------------------------------------------

def test_blocking_call_under_lock_fires_and_latches():
    w = locks.reset_for_tests(on=True)
    lk = locks.make_lock("blk.L")

    time.sleep(0)  # no traced lock held: probe is inert
    assert w.violations == []

    with lk:
        time.sleep(0)
        time.sleep(0)  # same fingerprint: latched

    assert len(w.violations) == 1
    assert "time.sleep" in w.violations[0]
    assert "blk.L" in w.violations[0]


def test_queue_get_under_lock_fires():
    import queue

    w = locks.reset_for_tests(on=True)
    lk = locks.make_lock("blk.Q")
    q = queue.Queue()
    q.put("x")  # put with nothing held: clean
    assert w.violations == []
    with lk:
        q.get()
    assert len(w.violations) == 1
    assert "Queue.get" in w.violations[0]


# ---------------------------------------------------------------------------
# Condition.wait
# ---------------------------------------------------------------------------

def test_condition_wait_on_own_lock_is_exempt():
    w = locks.reset_for_tests(on=True)
    cv = locks.make_condition(name="cv.solo")
    with cv:
        cv.wait(timeout=0.01)  # releases its own lock: sanctioned
    assert w.violations == []


def test_condition_wait_holding_other_lock_fires():
    w = locks.reset_for_tests(on=True)
    other = locks.make_lock("cv.other")
    cv = locks.make_condition(name="cv.pair")
    with other:
        with cv:
            cv.wait(timeout=0.01)  # sleeps with cv.other still held
    assert len(w.violations) == 1
    assert "Condition.wait" in w.violations[0]
    assert "cv.other" in w.violations[0]


def test_condition_notify_wakes_waiter_through_traced_lock():
    """The traced condition must still BE a condition: a waiter parked
    through the wrapper wakes on notify and reacquires the traced lock
    (held stacks stay balanced across the wait)."""
    locks.reset_for_tests(on=True)
    cv = locks.make_condition(name="cv.live")
    ready = threading.Event()
    state = {"woke": False}

    def waiter():
        with cv:
            ready.set()
            got = cv.wait(timeout=5)
            assert got
            assert cv.held_by_me()  # reacquired after the wait
            state["woke"] = True

    t = threading.Thread(target=waiter)
    t.start()
    assert ready.wait(timeout=5)
    # lock is only released once the waiter is parked inside wait()
    with cv:
        cv.notify_all()
    t.join(timeout=5)
    assert not t.is_alive()
    assert state["woke"]
    assert locks.witness().violations == []


# ---------------------------------------------------------------------------
# @guarded_by lockset
# ---------------------------------------------------------------------------

def test_lockset_unlocked_write_after_publication_fires():
    w = locks.reset_for_tests(on=True)

    @locks.guarded_by("_lock", "val")
    class Box:
        def __init__(self):
            self._lock = locks.make_lock("Box._lock")
            self.val = 0  # construction phase: thread-confined

    box = Box()
    box.val = 1  # same thread, never published: still legal
    assert w.violations == []
    with box._lock:
        box.val = 2  # published under its declared lock
    box.val = 3  # unguarded write after publication
    assert len(w.violations) == 1
    assert "Box.val" in w.violations[0]
    assert "published" in w.violations[0]


def test_lockset_second_thread_unlocked_write_fires():
    w = locks.reset_for_tests(on=True)

    @locks.guarded_by("_lock", "val")
    class Box2:
        def __init__(self):
            self._lock = locks.make_lock("Box2._lock")
            self.val = 0

    box = Box2()
    _run_in_thread(lambda: setattr(box, "val", 5))
    assert len(w.violations) == 1
    assert "Box2.val" in w.violations[0]
    assert "second thread" in w.violations[0]


def test_lockset_guarded_writes_from_any_thread_are_clean():
    w = locks.reset_for_tests(on=True)

    @locks.guarded_by("_lock", "val")
    class Box3:
        def __init__(self):
            self._lock = locks.make_lock("Box3._lock")
            self.val = 0

    box = Box3()

    def mutate():
        with box._lock:
            box.val += 1

    _run_in_thread(mutate)
    mutate()
    assert box.val == 2
    assert w.violations == []


# ---------------------------------------------------------------------------
# off path: raw primitives, zero per-acquisition overhead
# ---------------------------------------------------------------------------

def test_factories_return_raw_primitives_when_off():
    locks.reset_for_tests(on=False)
    assert type(locks.make_lock("x")) is type(threading.Lock())
    assert isinstance(locks.make_rlock("x"), type(threading.RLock()))
    assert isinstance(locks.make_condition(name="x"), threading.Condition)

    @locks.guarded_by("_lock", "val")
    class Cold:
        pass

    # metadata recorded for the static lockmodel pass, but no
    # __setattr__ hook installed
    assert Cold.__guarded_by__ == {"val": "_lock"}
    assert "__lockset_hooked__" not in Cold.__dict__


def test_raw_lock_into_condition_factory_stays_functional():
    # a raw lock built before enable() slipping into make_condition
    # afterwards must degrade to an untraced threading.Condition, not
    # crash the interop
    raw = threading.Lock()
    locks.reset_for_tests(on=True)
    cv = locks.make_condition(raw, name="late")
    assert isinstance(cv, threading.Condition)
    with cv:
        cv.wait(timeout=0.001)


def test_off_path_overhead_under_five_percent():
    locks.reset_for_tests(on=False)
    lk = locks.make_lock("perf.L")
    raw = threading.Lock()
    # the structural guarantee behind the number: off path, the factory
    # hands back the raw class itself — not a delegating wrapper
    assert type(lk) is type(raw)

    # both are the same class, so a gap is scheduler noise: on a box
    # that runs other tests beside this one a single best-of-9 misses
    # 5% now and then, and a real wrapper would miss it every time
    n, reps = 50_000, 9
    for _ in range(5):
        t_factory = min(timeit.repeat("lk.acquire(); lk.release()",
                                      globals={"lk": lk},
                                      number=n, repeat=reps))
        t_raw = min(timeit.repeat("lk.acquire(); lk.release()",
                                  globals={"lk": raw},
                                  number=n, repeat=reps))
        if t_factory <= t_raw * 1.05:
            break
    assert t_factory <= t_raw * 1.05, (
        f"off-path factory lock {t_factory:.4f}s vs raw {t_raw:.4f}s "
        f"(> 5% overhead)")
