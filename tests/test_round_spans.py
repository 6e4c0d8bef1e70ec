"""The round's spans on a JAX trace's clock (geomx_tpu.profiler.ROUND_SPANS).

A JAX trace is the one switch: while one runs, ``scope()`` / ``annotate()``
open a ``jax.profiler.TraceAnnotation``, whatever the chrome-trace half's
state; with no trace session they cost next to nothing; a process without
JAX imports nothing. One real two-party round
under ``jax.profiler.start_trace`` holds every ``work`` span of the
table, one ``trace_round`` id across worker, party server and global
server.
"""

import glob
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from geomx_tpu import profiler
from geomx_tpu.simulate import InProcessHiPS

from tests.harness import (RecordingApp, SingleTier, _poll, party_batch_push,
                           party_server_without_sockets)

# span events a round of the benchmark's GPT-2 cells (150 keys, two
# parties): two select spans a key and 43 others (counted in a traced
# run of gpt2s-hips-bsc on the chip, PR 34), and since PR 37 one more
# select span a party server, around its batch's draws and fan-out
SPANS_A_ROUND_CELL_1 = 345
OFF_BUDGET_S = 0.5e-3


@pytest.fixture(autouse=True)
def _clean_profiler():
    profiler.reset()
    yield
    profiler.reset()


class _Recorder:
    """Stands where ``jax.profiler.TraceAnnotation`` does."""

    opened = []
    order = []          # ("open" | "close", span) as they happened

    def __init__(self, name, **args):
        self.name, self.args = name, dict(args)

    def __enter__(self):
        self.thread = threading.get_ident()
        _Recorder.opened.append(self)
        _Recorder.order.append(("open", self))
        return self

    def __exit__(self, *exc):
        self.closed = True
        _Recorder.order.append(("close", self))
        return False

    def set_metadata(self, **args):
        self.args.update(args)

    @staticmethod
    def is_enabled():
        return True


@pytest.fixture
def recorder(monkeypatch):
    _Recorder.opened, _Recorder.order = [], []
    monkeypatch.setattr(profiler, "_annotation", _Recorder)
    return _Recorder


def _chrome_names():
    return [e["name"] for e in json.loads(profiler.dumps())["traceEvents"]]


def test_scope_annotates_without_the_chrome_half(recorder):
    assert not profiler.is_running()
    with profiler.scope("server.select", cat="kvstore", round=7) as span:
        span.set_metadata(node="l8p1")
    (ann,) = recorder.opened
    assert ann.name == "server.select" and ann.closed
    assert ann.args == {"round": 7, "node": "l8p1"}
    assert _chrome_names() == []


def test_scope_writes_both_while_running(recorder):
    profiler.set_state("run")
    with profiler.scope("server.select", cat="kvstore", round=7):
        pass
    assert [a.name for a in recorder.opened] == ["server.select"]
    (ev,) = json.loads(profiler.dumps())["traceEvents"]
    assert ev["name"] == "server.select" and ev["cat"] == "kvstore"
    assert ev["args"] == {"round": 7} and ev["dur"] >= 0


def test_annotate_is_the_real_class_while_a_trace_runs(tmp_path):
    import jax

    profiler._annotation = None
    with profiler.annotate("van.send", round=1) as span:
        assert span is profiler._NO_SPAN        # no session: a no-op
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.annotate("van.send", round=1) as span:
            assert isinstance(span, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()


def test_a_process_without_jax_imports_nothing(monkeypatch):
    monkeypatch.setattr(profiler, "_annotation", None)
    for name in [m for m in sys.modules
                 if m == "jax" or m.startswith("jax.")]:
        monkeypatch.delitem(sys.modules, name)
    with profiler.scope("server.push", round=3) as span:
        span.set_metadata(node="l8p1")      # accepted, goes nowhere
    with profiler.annotate("van.recv"):
        pass
    assert "jax" not in sys.modules and profiler._annotation is None


def test_the_off_path_fits_a_round_of_cell_one():
    """No trace session, chrome half stopped: the spans of one round of
    the GPT-2 cells add under half a millisecond of host time to a round
    of 1,200 ms. The best of several batches, so that a loaded box does
    not read as a slow path."""
    import jax  # noqa: F401 - the real annotation, as in a run

    from geomx_tpu.ps.van import Van

    van = Van.__new__(Van)      # identity alone: no socket, no thread
    van.is_global, van.my_id, van.root_port = False, 8, 9000
    profiler._annotation = None
    assert not profiler.is_running()
    best = float("inf")
    for _ in range(40):
        t0 = time.perf_counter()
        for i in range(SPANS_A_ROUND_CELL_1):
            # the arguments built a span, as the sites do
            with profiler.scope("server.select", cat="kvstore",
                                **van.round_args(i)):
                pass
        best = min(best, time.perf_counter() - t0)
    assert best < OFF_BUDGET_S, (
        f"{SPANS_A_ROUND_CELL_1} spans with no trace active took "
        f"{best * 1e6:.0f} us")


@pytest.mark.parametrize("global_servers", [1, 2])
def test_a_pooled_round_selects_under_server_select(recorder, monkeypatch,
                                                    global_servers):
    """A party server that re-selects its keys over a pool, the profiler
    running: one ``server.select`` a forwarded (key, slice) with the
    round's id and the server's node, whichever thread ran it, and one
    around the fan-out on the thread that took the push, so that thread
    is never under ``server.forward`` while a key is being selected:
    its innermost span during the join is ``server.select``."""
    from geomx_tpu.kvstore import server as server_mod

    monkeypatch.setattr(server_mod, "_POOL_MIN_ELEMS", 10_000)
    sizes = {3: 5_000, 7: 120_000, 9: 70_000, 12: 300, 13: 70_000}
    s = party_server_without_sockets(1, global_servers, keys=sizes)
    s.po_local.van.round_args = lambda r: {"round": r, "node": "l8p1"}
    s._select_pool = server_mod._SelectPool(2)
    rng = np.random.default_rng(3)
    pushes = {}
    for key, n in sizes.items():
        idx = rng.choice(n, n // 100, replace=False).astype(np.int32)
        pushes[key] = (rng.standard_normal(idx.size).astype(np.float32), idx)
    # this thread's small keys wait until a thread of the pool is at work
    me, helped, real = threading.get_ident(), threading.Event(), \
        s.gc.compress_push

    def compress_push(arr, state_key=None, **drawn):
        if threading.get_ident() != me:
            helped.set()
        elif arr.size < 10_000:
            assert helped.wait(30)
        return real(arr, state_key, **drawn)

    s.gc.compress_push = compress_push
    profiler.set_state("run")
    try:
        party_batch_push(s, RecordingApp(), 9, 0, pushes, trace_round=41)
    finally:
        profiler.set_state("stop")
        s._close_select_pool()
    selects = [a for a in recorder.opened if a.name == "server.select"]
    assert all(a.args == {"round": 41, "node": "l8p1"} and a.closed
               for a in selects)
    # the batch's own span, then one a (key, slice)
    assert len(selects) == 1 + len(sizes) * global_servers
    (forward,) = [a for a in recorder.opened if a.name == "server.forward"]
    fanout = selects[0]
    assert forward.thread == fanout.thread == me
    at = {id(span): {} for _what, span in recorder.order}
    for i, (what, span) in enumerate(recorder.order):
        at[id(span)][what] = i

    def inside(a, b):
        return (at[id(b)]["open"] < at[id(a)]["open"]
                and at[id(a)]["close"] < at[id(b)]["close"])

    assert inside(fanout, forward)
    assert all(inside(a, fanout) for a in selects[1:])
    # the pool took part; what this thread opened under the forward is
    # the fan-out and nothing beside it
    assert {a.thread for a in selects} - {me}
    mine = [a for a in recorder.opened
            if a.thread == me and inside(a, forward)]
    assert all(a is fanout or inside(a, fanout) for a in mine)


def test_the_table_is_constant_names_in_known_layers():
    names = [s.name for s in profiler.ROUND_SPANS]
    assert len(set(names)) == len(names)
    assert {s.cls for s in profiler.ROUND_SPANS} == {"work", "wait"}
    assert {s.layer for s in profiler.ROUND_SPANS} == {
        "trainer", "van", "party_server", "select", "global_server",
        "link"}
    assert all("{" not in n and "%" not in n for n in names)


def test_the_benchmarks_metric_files_read_the_table_as_it_is():
    """``benchmark/layer_metrics/gap.*.json`` list the spans each
    ``gap.*`` metric reads: every ``work`` span in exactly one file, the
    link's hold the only ``wait`` span read, no name the table lacks.
    A span renamed here goes silent there."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    listed = {}
    for path in glob.glob(os.path.join(root, "benchmark", "layer_metrics",
                                       "gap.*.json")):
        with open(path) as f:
            spec = json.load(f)
        for name in spec.get("spans", []):
            assert name not in listed, (name, path)
            listed[name] = spec["bucket"]
    table = {s.name: s for s in profiler.ROUND_SPANS}
    assert set(listed) <= set(table)
    assert {n for n, s in table.items() if s.cls == "work"} | {
        "link.hold"} == set(listed)
    assert all(table[n].layer == bucket for n, bucket in listed.items())


def test_retired_surface_is_gone():
    from geomx_tpu import telemetry

    for name in ("start_device_trace", "stop_device_trace",
                 "aggregate_stats", "chunk_scope"):
        assert not hasattr(profiler, name)
    assert not hasattr(telemetry, "link_goodput")


def test_van_recv_has_a_duration_and_the_merge_keys():
    """``van.recv`` runs from the first byte python sees of a frame to
    its hand-over, and still carries what tools/trace_merge.py pairs a
    send with its recv on."""
    key = lambda e: tuple(e["args"][k]                      # noqa: E731
                          for k in ("ovl", "from", "to", "mts", "req"))

    def spans(name):
        return {key(e) for e in json.loads(profiler.dumps())["traceEvents"]
                if e["name"] == name}

    with SingleTier(num_workers=1) as topo:
        (kv,) = topo.workers
        kv.init(0, np.ones(1 << 16, np.float32))
        kv.wait()
        profiler.set_state("run")
        kv.push(0, np.ones(1 << 16, np.float32))
        kv.pull(0)
        kv.wait()
        # a send's span is written when the write returns, on the
        # sender's thread: the receiver can have the frame, and this
        # thread its answer, before that. Stop once they are all in.
        _poll(lambda: spans("van.recv") <= spans("van.send"),
              "every received frame's van.send span")
        profiler.set_state("stop")
    evs = json.loads(profiler.dumps())["traceEvents"]
    recvs = [e for e in evs if e["name"] == "van.recv"]
    sends = [e for e in evs if e["name"] == "van.send"]
    assert recvs and sends
    assert all(e["dur"] > 0 for e in recvs)
    for e in recvs + sends:
        assert {"node", "ovl", "from", "to", "mts", "req", "verb",
                "bytes"} <= set(e["args"])
    assert {key(e) for e in recvs} <= {key(e) for e in sends}


# ---------------------------------------------------------------------------
# one real round under a JAX trace


def _host_spans(trace_dir):
    """(span name, thread line, stats) of the table's spans in the
    trace's host planes."""
    import jax

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    names = {s.name for s in profiler.ROUND_SPANS}
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for n, line in enumerate(plane.lines):
            out += [(e.name, (plane.name, n), dict(e.stats),
                     e.duration_ns) for e in line.events
                    if e.name in names]
    return out


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_traced_round_holds_every_work_span_with_one_round_id(tmp_path):
    import jax
    import jax.numpy as jnp

    from geomx_tpu.trainer_device import DeviceResidentTrainer

    sizes = [64, 32, 48]
    leaves0 = [np.linspace(-1, 1, n, dtype=np.float32) for n in sizes]

    def grad_step(leaves, X, y):
        return (sum(jnp.sum((l * X.mean()) ** 2) for l in leaves),
                [2 * l * X.mean() for l in leaves])

    # a shaped WAN, so that the link holds frames; no chrome half
    plan = json.dumps({"default": {"rtt_ms": 4, "bw_mbps": 1000}})
    topo = InProcessHiPS(num_parties=2, workers_per_party=1,
                         extra_cfg={"shape_plan": plan}).start()
    meet = threading.Barrier(2)

    def master_init(kv):
        kv.set_gradient_compression({"type": "bsc", "threshold": 0.25})
        for i, leaf in enumerate(leaves0):
            kv.init(i, leaf)
        kv.wait()

    def worker(kv):
        first = topo.workers.index(kv) == 0
        tr = DeviceResidentTrainer(list(leaves0), kv, grad_step,
                                   threshold=0.25, learning_rate=0.1)
        X = jnp.ones((2, 4))
        tr.step(X, None)                    # compiles, untraced
        meet.wait(60)
        if first:
            jax.profiler.start_trace(str(tmp_path))
        meet.wait(60)
        tr.step(X, None)
        kv.pull(0)                          # server.pull, both tiers
        kv.wait()
        meet.wait(60)
        if first:
            jax.profiler.stop_trace()
        meet.wait(60)

    try:
        topo.run_workers(worker, include_master=master_init, timeout=120)
    finally:
        topo.stop()
    assert not profiler.is_running() and _chrome_names() == []

    spans = _host_spans(str(tmp_path))
    seen = {name for name, _line, _stats, _dur in spans}
    work = {s.name for s in profiler.ROUND_SPANS if s.cls == "work"}
    # a combined round pulls nothing from the global tier by itself, and
    # the explicit pull above is answered by the party server's store
    assert work - seen <= {"server.pull.global"}, work - seen
    assert {"trainer.wait", "link.hold"} <= seen
    # one id across the tiers: the traced round's
    rounds = {}
    for name, _line, stats, _dur in spans:
        if stats.get("round", -1) >= 0:
            rounds.setdefault(name, set()).add(stats["round"])
    (rid,) = rounds["trainer.step"]
    for name in ("trainer.pack", "pipeline:send", "van.send", "van.recv",
                 "server.push", "server.select", "server.forward",
                 "server.push.global", "server.respond",
                 "server.pullback", "pipeline:recv", "trainer.apply",
                 "link.hold"):
        assert rid in rounds[name], (name, rounds[name], rid)
    # spans of a node carry its tag; two workers, two party servers and
    # one global server took part
    nodes = {stats["node"] for name, _l, stats, _d in spans
             if "node" in stats}
    assert len({n for n in nodes if n.startswith("g")}) >= 3
    assert len({n for n in nodes if n.startswith("l")}) >= 4
    # innermost-owns needs proper nesting on a thread: a van.recv never
    # has zero length on the trace's clock either
    assert all(dur > 0 for name, _l, _s, dur in spans
               if name == "van.recv")
