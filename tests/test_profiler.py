"""Profiler tests: chrome-trace recording + the remote command channel.

Reference behaviors covered: Profiler SetState/DumpProfile emitting
chrome-tracing JSON (src/profiler/profiler.h:270,304) and worker-driven
server profiler control with rank-prefixed dump files
(KVStoreServerProfilerCommand, include/mxnet/kvstore.h:49;
kvstore_dist_server.h:383-430).
"""

import json

import numpy as np
import pytest

from geomx_tpu import profiler
from geomx_tpu.optimizer import SGD

from tests.harness import SingleTier


@pytest.fixture(autouse=True)
def _clean_profiler():
    profiler.reset()
    yield
    profiler.reset()


def test_scope_records_chrome_trace_events(tmp_path):
    profiler.set_config(filename=str(tmp_path / "trace.json"))
    profiler.set_state("run")
    with profiler.scope("work", cat="test"):
        pass
    profiler.counter("queue_depth", 3)
    profiler.set_state("stop")
    path = profiler.dump()
    doc = json.loads(open(path).read())
    names = [e["name"] for e in doc["traceEvents"]]
    assert "work" in names and "queue_depth" in names
    ev = next(e for e in doc["traceEvents"] if e["name"] == "work")
    assert ev["ph"] == "X" and ev["dur"] >= 0 and ev["cat"] == "test"


def test_paused_and_stopped_record_nothing():
    profiler.set_state("run")
    profiler.pause()
    with profiler.scope("hidden"):
        pass
    profiler.resume()
    profiler.set_state("stop")
    with profiler.scope("hidden2"):
        pass
    assert json.loads(profiler.dumps())["traceEvents"] == []


def test_dump_clears_when_finished(tmp_path):
    profiler.set_config(filename=str(tmp_path / "t.json"))
    profiler.set_state("run")
    with profiler.scope("once"):
        pass
    profiler.dump(finished=True)
    assert json.loads(profiler.dumps())["traceEvents"] == []


def test_remote_command_rank_prefixes_dump(tmp_path):
    body = json.dumps({"cmd": profiler.CMD_SET_CONFIG,
                       "params": {"filename": str(tmp_path / "p.json")}})
    profiler.apply_remote_command(body, rank=2)
    profiler.apply_remote_command(
        json.dumps({"cmd": profiler.CMD_STATE, "params": {"state": "run"}}), 2)
    with profiler.scope("server_work"):
        pass
    profiler.apply_remote_command(
        json.dumps({"cmd": profiler.CMD_DUMP, "params": {}}), 2)
    out = tmp_path / "rank2_p.json"
    assert out.exists()
    doc = json.loads(out.read_text())
    assert any(e["name"] == "server_work" for e in doc["traceEvents"])


def test_remote_command_malformed_json_is_ignored():
    profiler.set_state("run")
    profiler.apply_remote_command("{not json", rank=0)
    profiler.apply_remote_command("", rank=0)
    # state untouched: still running, scopes record
    with profiler.scope("alive"):
        pass
    names = [e["name"] for e in json.loads(profiler.dumps())["traceEvents"]]
    assert names == ["alive"]


def test_remote_command_unknown_cmd_is_noop():
    profiler.set_state("run")
    profiler.apply_remote_command(json.dumps({"cmd": 99, "params": {}}), 0)
    profiler.apply_remote_command(json.dumps({"params": {}}), 0)  # no cmd
    assert profiler.is_running()


def test_remote_state_defaults_to_stop():
    profiler.set_state("run")
    profiler.apply_remote_command(json.dumps({"cmd": profiler.CMD_STATE}), 0)
    assert not profiler.is_running()


def test_remote_pause_defaults_true_and_roundtrips():
    profiler.set_state("run")
    profiler.apply_remote_command(json.dumps({"cmd": profiler.CMD_PAUSE}), 0)
    with profiler.scope("while_paused"):
        pass
    profiler.apply_remote_command(
        json.dumps({"cmd": profiler.CMD_PAUSE,
                    "params": {"paused": False}}), 0)
    with profiler.scope("after_resume"):
        pass
    names = [e["name"] for e in json.loads(profiler.dumps())["traceEvents"]]
    assert "while_paused" not in names and "after_resume" in names


def test_remote_set_config_without_filename_keeps_default():
    profiler.apply_remote_command(
        json.dumps({"cmd": profiler.CMD_SET_CONFIG,
                    "params": {"continuous_dump": True}}), rank=5)
    # no filename param -> nothing to rank-prefix, default stays; a
    # reference kwarg this profiler has no use for is stored, no more
    assert profiler._config["filename"] == "profile.json"
    assert profiler._config["continuous_dump"] is True


def test_dump_is_atomic_leaves_no_tmp(tmp_path):
    profiler.set_config(filename=str(tmp_path / "t.json"))
    profiler.set_state("run")
    with profiler.scope("w"):
        pass
    profiler.dump()
    assert [p.name for p in tmp_path.iterdir()] == ["t.json"]


def test_interrupted_dump_preserves_previous_trace(tmp_path, monkeypatch):
    """A dump that dies mid-write must not clobber an earlier good trace
    — the crash flight path dumps into files other tools then read."""
    target = tmp_path / "t.json"
    profiler.set_config(filename=str(target))
    profiler.set_state("run")
    with profiler.scope("good"):
        pass
    profiler.dump(finished=False)
    before = target.read_text()

    real_open = open

    def failing_open(path, *a, **kw):
        if ".tmp." in str(path):
            raise OSError("disk full")
        return real_open(path, *a, **kw)

    monkeypatch.setattr("builtins.open", failing_open)
    with pytest.raises(OSError):
        profiler.dump()
    monkeypatch.undo()
    assert target.read_text() == before
    doc = json.loads(target.read_text())
    assert any(e["name"] == "good" for e in doc["traceEvents"])


def test_worker_drives_server_profiler_end_to_end(tmp_path):
    """A worker remotely configures, runs, and dumps the server's
    profiler; the dump lands rank-prefixed and contains server.push
    scopes from real request handling."""
    with SingleTier(num_workers=1) as topo:
        (kv,) = topo.workers
        kv.set_optimizer(SGD(learning_rate=1.0))
        kv.set_profiler_params(profiler.CMD_SET_CONFIG,
                               filename=str(tmp_path / "srv.json"))
        kv.set_profiler_params(profiler.CMD_STATE, state="run")
        kv.init(0, np.ones(4, np.float32))
        kv.push(0, np.ones(4, np.float32))
        out = kv.pull(0)
        kv.wait()
        np.testing.assert_allclose(out, np.zeros(4))
        kv.set_profiler_params(profiler.CMD_STATE, state="stop")
        kv.set_profiler_params(profiler.CMD_DUMP)
        dump = tmp_path / "rank0_srv.json"
        assert dump.exists()
        doc = json.loads(dump.read_text())
        names = {e["name"] for e in doc["traceEvents"]}
        assert "server.push" in names
        # per-operator engine tags (reference op tagging at
        # kvstore_dist_server.h:570): key-level spans + the updater span
        assert "push:key0" in names
        assert "pull:key0" in names
        assert "update:key0" in names


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
