"""Crash flight recorder: ring semantics, dump discipline, and the
acceptance scenario — a FaultPlan crash leaves a dump whose last events
are the in-flight round's wire frames.
"""

import glob
import json
import os
import signal

import numpy as np
import pytest

from geomx_tpu.optimizer import SGD
from geomx_tpu.ps import base as psbase
from geomx_tpu.ps import flightrec
from geomx_tpu.ps.flightrec import FlightRecorder, default_dir
from tools import flight_report

from tests.harness import SingleTier, _parallel


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------

def test_ring_bounds_and_seq_ordering():
    rec = FlightRecorder(lambda: "n1", size=4)
    assert rec.enabled
    for i in range(10):
        rec.record("sent", peer=i)
    evs = rec.snapshot()
    assert len(evs) == 4
    # the ring keeps the LAST events; seq keeps counting across drops
    assert [e["peer"] for e in evs] == [6, 7, 8, 9]
    assert [e["seq"] for e in evs] == [7, 8, 9, 10]
    assert all(e["kind"] == "sent" and "t" in e for e in evs)


def test_size_zero_disables(tmp_path):
    rec = FlightRecorder(lambda: "n1", size=0, out_dir=str(tmp_path))
    assert not rec.enabled
    rec.record("sent", peer=1)
    assert rec.snapshot() == []
    assert rec.dump("crash:off") == ""
    assert list(tmp_path.iterdir()) == []


def test_dump_writes_atomic_json(tmp_path):
    rec = FlightRecorder(lambda: "g8p9000", size=8, out_dir=str(tmp_path))
    rec.record("sent", peer=10, verb="push", bytes=64, round=3)
    path = rec.dump("violation:unanswered-request")
    assert os.path.basename(path) == f"flightrec_g8p9000_pid{os.getpid()}.json"
    doc = json.loads(open(path).read())
    assert doc["node"] == "g8p9000"
    assert doc["reason"] == "violation:unanswered-request"
    assert doc["events"][0]["round"] == 3
    assert all(".tmp." not in p.name for p in tmp_path.iterdir())


def test_dump_dedups_by_reason_class(tmp_path):
    rec = FlightRecorder(lambda: "n1", size=8, out_dir=str(tmp_path))
    rec.record("crash", reason="x")
    first = rec.dump("crash:rule #0")
    assert first
    # a cascade within the class must not rewrite the first dump
    assert rec.dump("crash:rule #1") == ""
    # a different class still dumps (explicit path: don't collide on name)
    other = rec.dump("round_abort", path=str(tmp_path / "abort.json"))
    assert other and other != first


def test_dump_never_raises(tmp_path, monkeypatch):
    rec = FlightRecorder(lambda: "n1", size=8,
                         out_dir=str(tmp_path / "sub"))
    rec.record("sent", peer=1)

    real_open = open

    def failing_open(path, *a, **kw):
        if ".tmp." in str(path):
            raise OSError("disk full")
        return real_open(path, *a, **kw)

    monkeypatch.setattr("builtins.open", failing_open)
    assert rec.dump("crash:boom") == ""     # swallowed, logged
    monkeypatch.undo()
    # the failed attempt must not burn the reason class
    assert rec.dump("crash:boom") != ""


def test_node_fn_failure_falls_back_to_unknown(tmp_path):
    def exploding():
        raise RuntimeError("no rendezvous yet")

    rec = FlightRecorder(exploding, size=4, out_dir=str(tmp_path))
    rec.record("note", event="early")
    path = rec.dump("crash:pre-start")
    assert "flightrec_unknown_pid" in path


def test_default_dir_under_tmp():
    assert default_dir().endswith("geomx_flightrec")


# ---------------------------------------------------------------------------
# shutdown dumps (reason class "shutdown": SIGTERM / atexit)
# ---------------------------------------------------------------------------

def test_shutdown_dump_all_writes_own_file(tmp_path):
    rec = FlightRecorder(lambda: "n1", size=8, out_dir=str(tmp_path))
    rec.record("sent", peer=8)
    paths = flightrec.dump_all("shutdown:atexit")
    mine = [p for p in paths if str(tmp_path) in p]
    assert len(mine) == 1
    assert mine[0].endswith("_shutdown.json")
    doc = json.loads(open(mine[0]).read())
    assert doc["reason"] == "shutdown:atexit"
    assert doc["events"][0]["peer"] == 8
    # the shutdown class is latched like any other: a second pass (the
    # atexit hook after a SIGTERM dump) must not re-dump
    assert [p for p in flightrec.dump_all("shutdown:atexit")
            if str(tmp_path) in p] == []


def test_shutdown_skips_empty_rings_and_default_dir(tmp_path):
    # empty ring: enrolled but nothing worth a post-mortem
    FlightRecorder(lambda: "empty", size=8, out_dir=str(tmp_path))
    # default out_dir: NOT enrolled (ordinary runs must not litter $TMPDIR)
    implicit = FlightRecorder(lambda: "implicit", size=8)
    implicit.record("sent", peer=1)
    assert implicit not in flightrec._shutdown_registry
    assert [p for p in flightrec.dump_all("shutdown:atexit")
            if str(tmp_path) in p] == []


def test_shutdown_dump_does_not_clobber_crash_dump(tmp_path):
    rec = FlightRecorder(lambda: "n2", size=8, out_dir=str(tmp_path))
    rec.record("crash", reason="x")
    crash = rec.dump("crash:rule #0")
    shut = rec.dump("shutdown:sigterm")
    assert crash and shut and shut != crash
    assert json.loads(open(crash).read())["reason"] == "crash:rule #0"


def test_sigterm_dumps_and_preserves_kill_status(tmp_path):
    """A SIGTERM'd process leaves a shutdown dump AND still dies by
    SIGTERM (the handler re-delivers the default disposition)."""
    code = (
        "import os, signal, sys, time\n"
        "from geomx_tpu.ps.flightrec import FlightRecorder\n"
        "rec = FlightRecorder(lambda: 'victim', size=8,"
        f" out_dir={str(tmp_path)!r})\n"
        "rec.record('sent', peer=8, verb='push')\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
        "time.sleep(5)\n"
        "sys.exit(3)  # unreachable unless the re-kill was swallowed\n"
    )
    import subprocess
    import sys as _sys
    proc = subprocess.run([_sys.executable, "-c", code], timeout=60,
                          capture_output=True)
    assert proc.returncode == -signal.SIGTERM, proc.stderr.decode()
    dumps = glob.glob(str(tmp_path / "*_shutdown.json"))
    assert len(dumps) == 1
    doc = json.loads(open(dumps[0]).read())
    assert doc["reason"] == "shutdown:sigterm"
    assert doc["events"][0]["peer"] == 8


# ---------------------------------------------------------------------------
# flight_report rendering
# ---------------------------------------------------------------------------

def test_flight_report_renders_narrative(tmp_path, capsys):
    rec = FlightRecorder(lambda: "l9p5001", size=8, out_dir=str(tmp_path))
    rec.record("sent", peer=8, verb="push", bytes=4096, req=True,
               ts=12, round=5, chunk=-1, origin=9, epoch=0)
    rec.record("recv", peer=8, verb="push", bytes=16, req=False,
               ts=12, round=5, chunk=-1, origin=9, epoch=0)
    rec.record("crash", reason="crash rule #0")
    path = rec.dump("crash:rule #0")

    text = flight_report.report(json.loads(open(path).read()))
    assert "node l9p5001" in text
    assert "crash:rule #0" in text
    assert "rounds in flight: [5]" in text
    assert "push" in text and "round=5" in text

    # CLI over a directory finds the dump; --tail trims events
    rc = flight_report.main([str(tmp_path), "--tail", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "showing last 2" in out and "crash" in out


def test_flight_report_cli_errors_on_missing(tmp_path, capsys):
    assert flight_report.main([str(tmp_path)]) == 1  # empty dir
    bad = tmp_path / "flightrec_x_pid1.json"
    bad.write_text("{not json")
    assert flight_report.main([str(bad)]) == 1
    assert "unreadable" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# acceptance: a FaultPlan crash dumps the in-flight round's frames
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_faultplan_crash_dumps_in_flight_round(tmp_path):
    """Kill a worker with an ``at_round`` crash rule after a full traced
    round: its van must leave a flight-recorder dump whose tail is the
    round's wire frames (with the trace round id) ending in the crash."""
    victim_id = psbase.worker_rank_to_id(1)
    plan = json.dumps({"rules": [{
        "type": "crash", "node": victim_id, "at_round": 2,
        "tier": "local"}]})
    w0 = np.zeros(8, np.float32)
    with SingleTier(extra={"fault_plan": plan, "ps_seed": 11,
                           "flightrec_dir": str(tmp_path)}) as topo:
        workers = sorted(topo.workers, key=lambda kv: kv.rank)
        rank0, victim = workers
        rank0.set_optimizer(SGD(learning_rate=1.0))
        _parallel([lambda kv=kv: kv.init(0, w0) for kv in workers])

        # round 1: a traced push_pull from every worker puts round-
        # stamped frames in the victim's ring
        def step(kv):
            kv.push_pull(0, np.ones_like(w0), np.zeros_like(w0))
            kv.wait()

        _parallel([lambda kv=kv: step(kv) for kv in workers])

        victim.notify_round(2)           # at_round rule fires here
        assert victim.po.van.stopped.wait(10), "crash rule did not fire"

        dumps = glob.glob(str(tmp_path / "flightrec_*.json"))
        docs = [json.loads(open(p).read()) for p in dumps]
        crash = [d for d in docs if d["reason"].startswith("crash")]
        assert len(crash) == 1, f"expected one crash dump, got {dumps}"
        doc = crash[0]
        events = doc["events"]
        assert events[-1]["kind"] == "crash"
        # the tail is the in-flight round: the victim's own sends,
        # carrying the trace round id the worker stamped
        sends = [e for e in events if e["kind"] == "sent"
                 and e.get("round", -1) >= 1]
        assert sends, "no round-stamped sends in the crash dump"
        assert any(e["verb"] in ("push", "pull") for e in sends)


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
