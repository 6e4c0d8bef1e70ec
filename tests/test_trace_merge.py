"""tools/trace_merge: clock alignment on synthetic skew + the
acceptance scenario — one 2-party HiPS round visible end-to-end in the
merged trace.
"""

import json

import numpy as np
import pytest

from geomx_tpu import profiler
from geomx_tpu.optimizer import SGD
from geomx_tpu.simulate import InProcessHiPS
from tools import trace_merge

from tests.harness import _parallel


@pytest.fixture(autouse=True)
def _clean_profiler():
    profiler.reset()
    yield
    profiler.reset()


# ---------------------------------------------------------------------------
# synthetic clock alignment
# ---------------------------------------------------------------------------

def _span(name, node, ts, dur, *, ovl="127.0.0.1:5000:l", frm, to, mts,
          req, **extra):
    return {"name": name, "cat": "transport", "ph": "X", "ts": ts,
            "dur": dur, "pid": 1, "tid": 1,
            "args": {"node": node, "ovl": ovl, "from": frm, "to": to,
                     "mts": mts, "req": req, **extra}}


def _skewed_pair(skew_us=50_000.0, lat_us=100.0):
    """Node A at true time; node B's clock runs ``skew_us`` ahead. Two
    request/response exchanges cross the link, each leg taking
    ``lat_us`` of flight time. All send spans have dur=10 (the wire time
    is the span END)."""
    a_evs, b_evs = [], []
    for i, t0 in enumerate((1000.0, 5000.0)):
        mts = 100 + i
        # A sends a request at t0 (10us of pack time), B receives it
        # lat_us after the send completes — on B's clock, +skew
        a_evs.append(_span("van.send", "A", t0, 10,
                           frm=9, to=8, mts=mts, req=True))
        b_evs.append(_span("van.recv", "B", t0 + 10 + lat_us + skew_us, 5,
                           frm=9, to=8, mts=mts, req=True))
        # B responds 50us later; A receives lat_us after that
        bt = t0 + 10 + lat_us + skew_us + 50
        b_evs.append(_span("van.send", "B", bt, 10,
                           frm=8, to=9, mts=mts, req=False))
        a_evs.append(_span("van.recv", "A", bt + 10 + lat_us - skew_us, 5,
                           frm=8, to=9, mts=mts, req=False))
    return {"A": a_evs, "B": b_evs}


def test_solve_offsets_recovers_synthetic_skew():
    nodes = _skewed_pair(skew_us=50_000.0, lat_us=100.0)
    offsets, matched = trace_merge.solve_offsets(nodes, reference="A")
    assert matched == 4
    assert offsets["A"] == 0.0
    # symmetric latency cancels exactly: the offset IS the skew
    assert offsets["B"] == pytest.approx(50_000.0)


def test_merge_reorders_recv_after_send():
    nodes = _skewed_pair(skew_us=50_000.0, lat_us=100.0)
    doc = trace_merge.merge(nodes, reference="A")
    assert doc["metadata"]["clock_offsets_us"]["B"] == pytest.approx(50_000)
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by = {}
    for e in evs:
        by.setdefault((e["args"]["mts"], e["args"]["req"]), {})[
            e["name"]] = e
    # after alignment every recv lands after its send's wire end, by
    # exactly the synthetic one-way latency
    for pair in by.values():
        send, recv = pair["van.send"], pair["van.recv"]
        flight = recv["ts"] - (send["ts"] + send["dur"])
        assert flight == pytest.approx(100.0)
    # per-node pids + process_name metadata rows for Perfetto
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("name") == "process_name"}
    assert names == {"A", "B"}
    pids = {e["pid"] for e in evs}
    assert len(pids) == 2


def test_one_directional_link_keeps_node_on_timeline():
    nodes = _skewed_pair()
    # drop B's responses: only A->B frames remain
    nodes["B"] = [e for e in nodes["B"] if e["name"] == "van.recv"]
    nodes["A"] = [e for e in nodes["A"] if e["name"] == "van.send"]
    offsets, matched = trace_merge.solve_offsets(nodes, reference="A")
    assert matched == 2
    # zero-latency assumption: the whole observed delta becomes offset
    assert offsets["B"] == pytest.approx(50_000.0 + 100.0 + 0, abs=20)


def test_unlinked_node_defaults_to_zero_offset():
    nodes = _skewed_pair()
    nodes["C"] = [{"name": "other", "ph": "X", "ts": 1.0, "dur": 1.0,
                   "args": {"node": "C"}}]
    offsets, _ = trace_merge.solve_offsets(nodes, reference="A")
    assert offsets["C"] == 0.0


def test_disconnected_node_warns_and_still_merges(capsys):
    """A node with no matched send/recv pair to the reference (e.g. it
    crashed before answering anything) must not fail the merge: it is
    kept at offset 0, warned about on stderr, and flagged in the
    metadata for downstream consumers."""
    nodes = _skewed_pair()
    # C talks only to itself: wire pairs exist but never cross to A/B
    nodes["C"] = [
        _span("van.send", "C", 100.0, 10, frm=5, to=5, mts=900, req=True),
        _span("van.recv", "C", 200.0, 5, frm=5, to=5, mts=900, req=True),
    ]
    doc = trace_merge.merge(nodes, reference="A")
    err = capsys.readouterr().err
    assert "node C" in err and "offset 0" in err
    assert doc["metadata"]["unanchored_nodes"] == ["C"]
    assert doc["metadata"]["clock_offsets_us"]["C"] == 0.0
    # C's events made it into the merged trace on their own pid
    c_pids = {e["pid"] for e in doc["traceEvents"]
              if (e.get("args") or {}).get("node") == "C"}
    assert len(c_pids) == 1
    # the connected pair still aligns normally, and nothing else is
    # flagged
    assert doc["metadata"]["clock_offsets_us"]["B"] == pytest.approx(50_000)


def test_load_nodes_splits_by_node_arg(tmp_path):
    merged = tmp_path / "all.json"
    merged.write_text(json.dumps({"traceEvents": [
        _span("van.send", "A", 1, 1, frm=1, to=2, mts=1, req=True),
        _span("van.recv", "B", 2, 1, frm=1, to=2, mts=1, req=True),
        {"name": "anon", "ph": "X", "ts": 0, "dur": 1},
    ]}))
    nodes = trace_merge.load_nodes([str(merged)])
    # tagged events split by node; untagged fall to the file's name
    assert set(nodes) == {"A", "B", "all"}


def test_rounds_spanning_reads_round_args():
    doc = {"traceEvents": [
        _span("van.send", "A", 1, 1, frm=1, to=2, mts=1, req=True,
              round=3),
        _span("van.recv", "B", 2, 1, frm=1, to=2, mts=1, req=True,
              round=3),
        _span("van.send", "B", 9, 1, frm=2, to=1, mts=2, req=True),
    ]}
    assert trace_merge.rounds_spanning(doc) == {3: {"A", "B"}}


# ---------------------------------------------------------------------------
# acceptance: a 2-party round merges into one trace, visible end-to-end
# ---------------------------------------------------------------------------

def test_two_party_round_traces_end_to_end(tmp_path):
    """Run one traced push_pull round on a 2-party HiPS sim, split the
    profiler dump per node, merge with trace_merge, and assert one
    round id shows up on worker, local-server and global-tier nodes —
    the PR's core acceptance criterion."""
    profiler.set_state("run")
    sim = InProcessHiPS(num_parties=2, workers_per_party=1).start(
        sync_global=True)
    try:
        sim.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.zeros(64, np.float32)

        def init_on(kv):
            kv.init(0, w0)
            kv.wait()

        _parallel([lambda kv=kv: init_on(kv)
                   for kv in sim.workers + [sim.master]])

        def step(kv):
            kv.push_pull(0, np.ones(64, np.float32),
                         np.zeros(64, np.float32))
            kv.wait()

        _parallel([lambda kv=kv: step(kv) for kv in sim.workers])
    finally:
        sim.stop()
    profiler.set_state("stop")
    all_path = tmp_path / "all.json"
    profiler.dump(filename=str(all_path))

    # split the in-process dump into per-node files (a real deployment's
    # shape) and merge them back through the CLI entry point
    nodes = trace_merge.load_nodes([str(all_path)])
    van_nodes = {n: evs for n, evs in nodes.items()
                 if any(e.get("name") in ("van.send", "van.recv")
                        for e in evs)}
    assert len(van_nodes) >= 5, f"expected a full topology, got {van_nodes.keys()}"
    paths = []
    for node, evs in van_nodes.items():
        p = tmp_path / f"{node}.json"
        p.write_text(json.dumps({"traceEvents": evs}))
        paths.append(str(p))
    out = tmp_path / "merged.json"
    assert trace_merge.main([*paths, "-o", str(out)]) == 0

    doc = json.loads(out.read_text())
    assert doc["metadata"]["matched_wire_pairs"] > 0
    spans = trace_merge.rounds_spanning(doc)
    assert spans, "no round ids in the merged trace"
    best = max(spans.values(), key=len)
    # end-to-end: both parties' worker and server nodes plus the global
    # tier carry the same round id
    assert len(best) >= 5
    assert any(n.startswith("g") for n in best), f"no global node in {best}"
    assert any(n.startswith("l") for n in best), f"no local node in {best}"


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
