"""The round's spans on a JAX trace's clock (geomx_tpu.profiler.ROUND_SPANS).

A JAX trace is the one switch: while one runs, ``scope()`` / ``annotate()``
open a ``jax.profiler.TraceAnnotation``, whatever the chrome-trace half's
state; with no trace session they cost next to nothing; a process without
JAX imports nothing. One real two-party round
under ``jax.profiler.start_trace`` holds every ``work`` span of the
table, one ``trace_round`` id across worker, party server and global
server.
"""

import gc
import glob
import json
import logging
import os
import sys
import threading
import time

import numpy as np
import pytest

from geomx_tpu import profiler, telemetry
from geomx_tpu.simulate import InProcessHiPS

from tests.harness import (RecordingApp, SingleTier, _poll, party_batch_push,
                           party_server_without_sockets)

# span events a round of the benchmark's GPT-2 cells (150 keys, two
# parties): two select spans a key and 43 others (counted in a traced
# run of gpt2s-hips-bsc on the chip, PR 34), and since PR 37 one more
# select span a party server, around its batch's draws and fan-out
SPANS_A_ROUND_CELL_1 = 345
OFF_BUDGET_S = 0.5e-3
ON_BUDGET_S = 1.5e-3


@pytest.fixture(autouse=True)
def _clean_profiler():
    telemetry.reset()
    profiler.reset()
    yield
    telemetry.reset()
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


def test_the_trainers_key_counters_are_what_their_metric_files_read():
    """The three counters a trainer's round books a key (the selection
    by threshold, the dense reset, the upload written in place) are the
    ``prefix`` of the benchmark's metric files of those names, read by
    its counter reader, and the new one is in the manifest: a round of a
    trainer moves each by its number of keys, in one chunk or several,
    so the held upload's reads the dense reset's numbers in every cell."""
    from types import SimpleNamespace

    import jax.numpy as jnp

    from geomx_tpu.kvstore import create as kv_create
    from geomx_tpu.trainer_device import DeviceResidentTrainer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    prefixes = {}
    for name, layer in (("step.select_threshold_keys", "device step"),
                        ("step.dense_reset_keys", "device step"),
                        ("round.trainer_upload_inplace_keys", "trainer")):
        with open(os.path.join(root, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert (spec["name"], spec["reader"], spec["layer"]) == (
            name, "counter_per_round", layer)
        assert "must_contain" not in spec and "zero_with" not in spec
        entry = per_layer[name]
        assert all(entry[k] == spec[k]
                   for k in ("unit", "source", "layer", "moves"))
        assert entry["source"] == "program_counter"
        assert "workloads" not in entry     # every cell runs a trainer
        prefixes[name] = spec["prefix"]
    assert prefixes["round.trainer_upload_inplace_keys"] == \
        "trainer.upload_inplace_keys"
    kv = kv_create("local")
    kv.cfg = SimpleNamespace(wire_codec="", p3_slice_bytes=96)
    shapes = [(40, 16), (129,), (7,)]
    tr = DeviceResidentTrainer(
        [np.ones(s, np.float32) for s in shapes], kv,
        lambda leaves, X, y: (sum(jnp.sum(l * l) for l in leaves) * X,
                              [2 * l * X for l in leaves]),
        threshold=0.1, learning_rate=0.1)
    assert len(tr._chunks) > 1
    was_on = telemetry.enabled()
    telemetry.enable(True)
    try:
        def read():
            counters = telemetry.snapshot()["counters"]
            return {name: sum(v for k, v in counters.items()
                              if k.startswith(prefix))
                    for name, prefix in prefixes.items()}

        before = read()
        for rounds in (1, 2):
            tr.step(jnp.asarray(0.5), None)
            assert {name: v - before[name] for name, v in read().items()
                    } == dict.fromkeys(prefixes, len(shapes) * rounds)
    finally:
        telemetry.enable(was_on)


def test_the_rounds_chunk_counter_is_what_its_metric_file_reads():
    """``trainer.round_chunks`` moves by ``len(tr._chunks)`` a round,
    once, whatever the number of keys; ``round.trainer_chunks`` is its
    data file, in the manifest with no list of cells (every cell runs a
    trainer)."""
    import jax.numpy as jnp

    from geomx_tpu.config import Config
    from geomx_tpu.kvstore import create as kv_create
    from geomx_tpu.trainer_device import DeviceResidentTrainer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[
            "round.trainer_chunks"]
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "round.trainer_chunks.json")) as f:
        spec = json.load(f)
    assert entry == {"name": "round.trainer_chunks", "unit": "count",
                     "better": "higher", "source": "program_counter",
                     "layer": "trainer", "moves": "tokens_per_s_per_chip"}
    assert all(entry[k] == spec[k] for k in entry if k != "better")
    assert (spec["reader"], spec["prefix"]) == ("counter_per_round",
                                                "trainer.round_chunks")
    assert "must_contain" not in spec and "zero_with" not in spec

    def rounds_of(cfg):
        kv = kv_create("local")
        kv.cfg = cfg
        tr = DeviceResidentTrainer(
            [np.ones(s, np.float32) for s in [(40, 16), (129,), (7,)]],
            kv, lambda leaves, X, y: (
                sum(jnp.sum(l * l) for l in leaves) * X,
                [2 * l * X for l in leaves]),
            threshold=0.1, learning_rate=0.1)

        def read():
            return sum(v for k, v in telemetry.snapshot()["counters"]
                       .items() if k.startswith(spec["prefix"]))

        before = read()
        for rounds in (1, 2, 3):
            tr.step(jnp.asarray(0.5), None)
            assert read() - before == len(tr._chunks) * rounds
        return len(tr._chunks)

    was_on = telemetry.enabled()
    telemetry.enable(True)
    try:
        assert rounds_of(Config()) == 1
        assert rounds_of(Config(p3_slice_bytes=96)) == 3
    finally:
        telemetry.enable(was_on)


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


def _two_party_rounds(body, **extra_cfg):
    """Two parties of one worker each on a tiny model, Bi-Sparse on both
    tiers: ``body(tr, X, first, meet)`` runs on each worker's thread
    with its ``DeviceResidentTrainer`` after one step that compiled."""
    import jax.numpy as jnp

    from geomx_tpu.trainer_device import DeviceResidentTrainer

    sizes = [64, 32, 48]
    leaves0 = [np.linspace(-1, 1, n, dtype=np.float32) for n in sizes]

    def grad_step(leaves, X, y):
        return (sum(jnp.sum((l * X.mean()) ** 2) for l in leaves),
                [2 * l * X.mean() for l in leaves])

    topo = InProcessHiPS(num_parties=2, workers_per_party=1,
                         extra_cfg=extra_cfg).start()
    meet = threading.Barrier(2)

    def master_init(kv):
        kv.set_gradient_compression({"type": "bsc", "threshold": 0.25})
        for i, leaf in enumerate(leaves0):
            kv.init(i, leaf)
        kv.wait()

    def worker(kv):
        tr = DeviceResidentTrainer(list(leaves0), kv, grad_step,
                                   threshold=0.25, learning_rate=0.1)
        X = jnp.ones((2, 4))
        tr.step(X, None)                    # compiles
        meet.wait(60)
        body(tr, X, topo.workers.index(kv) == 0, meet)

    try:
        topo.run_workers(worker, include_master=master_init, timeout=120)
    finally:
        topo.stop()


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_traced_round_holds_every_work_span_with_one_round_id(tmp_path):
    import jax

    def traced_round(tr, X, first, meet):
        if first:
            jax.profiler.start_trace(str(tmp_path))
        meet.wait(60)
        tr.step(X, None)
        tr.kv.pull(0)                       # server.pull, both tiers
        tr.kv.wait()
        meet.wait(60)
        if first:
            jax.profiler.stop_trace()
        meet.wait(60)

    # a shaped WAN, so that the link holds frames; no chrome half
    _two_party_rounds(traced_round, shape_plan=json.dumps(
        {"default": {"rtt_ms": 4, "bw_mbps": 1000}}))
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


# ---------------------------------------------------------------------------
# the round account: the same spans on the host's clocks, no trace running


class _Clock:
    """Feeds the profiler's two clocks."""

    def __init__(self, monkeypatch):
        self.wall = self.cpu = 0
        monkeypatch.setattr(profiler, "_wall_ns", lambda: self.wall)
        monkeypatch.setattr(profiler, "_cpu_ns", lambda: self.cpu)

    def run(self, wall_ms, cpu_ms=0.0):
        self.wall += int(wall_ms * 1e6)
        self.cpu += int(cpu_ms * 1e6)


class _RealClocks:
    """In ``_Clock``'s place where a test keeps the real clocks."""

    @staticmethod
    def run(wall_ms, cpu_ms=0.0):
        pass


@pytest.fixture
def clock(monkeypatch):
    telemetry.enable(True)
    return _Clock(monkeypatch)


def _counters(family):
    """{span: value} of one of the account's counter families."""
    head = family + "{span="
    return {k[len(head):-1]: v
            for k, v in telemetry.snapshot()["counters"].items()
            if k.startswith(head)}


def _held(rid):
    """{node: {span: closed}} of what the threads hold of one round."""
    nodes, _host = profiler._gathered(rid, rid + 1)
    return {node: {name: e[0] for name, e in spans.items()}
            for node, spans in nodes.items()}


def _step(clock, rid, ms, node="l9p0", inside=lambda: None, chunks=1):
    """One worker's round on the fed clock: ``ms`` long, all but one of
    them asleep inside the last chunk's ``trainer.unpack``. A chunk has
    an id of its own, the step its first chunk's."""
    with profiler.scope("trainer.step", cat="trainer", node=node,
                        round=-1) as whole:
        whole.set_metadata(round=rid)
        for ci in range(chunks):
            with profiler.scope("trainer.unpack", cat="trainer", node=node,
                                round=rid + ci, chunk=ci):
                if ci == chunks - 1:
                    clock.run(ms - 1)
                    inside()
        clock.run(1, 1)


def test_a_span_books_its_self_time_on_both_clocks(clock):
    with profiler.scope("server.push", node="l8p0", round=3):
        clock.run(5, 4)
        with profiler.scope("server.forward", node="l8p0", round=3):
            clock.run(7, 1)
            with profiler.annotate("van.send", node="l8p0", round=3):
                clock.run(2, 2)
        clock.run(1, 1)
    # a child's wall and CPU time leave its parent's
    assert _counters("round.work_ms") == pytest.approx(
        {"server.push": 6, "server.forward": 7, "van.send": 2})
    assert _counters("round.work_cpu_ms") == pytest.approx(
        {"server.push": 5, "server.forward": 1, "van.send": 2})
    assert _counters("round.spans") == {
        "server.push": 1, "server.forward": 1, "van.send": 1}
    assert _counters("round.wait_ms") == {}


def test_wait_spans_have_their_own_counter(clock):
    with profiler.scope("trainer.step", node="l9p0", round=2):
        clock.run(1, 1)
        with profiler.scope("trainer.wait", node="l9p0", round=2):
            clock.run(30)
    with profiler.annotate("link.hold", sender=9, node="g8", round=2):
        clock.run(12)
    assert _counters("round.wait_ms") == pytest.approx(
        {"trainer.wait": 30, "link.hold": 12})
    assert _counters("round.work_ms") == pytest.approx({"trainer.step": 1})
    assert set(_counters("round.work_cpu_ms")) == {"trainer.step"}
    assert _counters("round.spans") == {
        "trainer.step": 1, "trainer.wait": 1, "link.hold": 1}


def test_a_span_without_a_round_takes_its_enclosing_spans(clock):
    with profiler.scope("trainer.step", node="l9p0", round=-1) as whole:
        with profiler.scope("trainer.fetch", node="l9p0", chunk=0):
            clock.run(3)                # closes before the id is known
        with profiler.scope("trainer.pack", node="l9p0", chunk=0) as span:
            with profiler.annotate("van.send", node="l9p0", round=6):
                clock.run(1, 1)
            span.set_metadata(round=6)
        whole.set_metadata(round=5)     # the step's own, given late
    with profiler.annotate("van.recv") as span:
        span.set_metadata(node="l8p0", round=6)
    with profiler.annotate("van.recv"):     # a control frame: no round
        clock.run(4)
    assert _held(5) == {"l9p0": {"trainer.step": 1, "trainer.fetch": 1}}
    assert _held(6) == {"l9p0": {"trainer.pack": 1, "van.send": 1},
                        "l8p0": {"van.recv": 1}}
    # the frame of no round is in the counters all the same
    assert _counters("round.spans")["van.recv"] == 2
    assert _counters("round.work_ms")["van.recv"] == pytest.approx(4)


def test_a_span_that_closes_after_its_round_went_is_booked_next(clock):
    late = profiler.annotate("van.send", node="l8p0", round=1)
    late.__enter__()
    clock.run(2, 2)
    done = threading.Event()

    def worker():
        for rid in (1, 2):
            _step(clock, rid, 10)
        done.set()

    t = threading.Thread(target=worker)
    t.start()
    t.join(30)
    assert done.is_set()
    # round 1 went when the step of round 2 closed
    assert profiler._keep_from == 2
    assert "van.send" not in _counters("round.spans")
    clock.run(3, 1)
    late.__exit__(None, None, None)
    assert _counters("round.spans")["van.send"] == 1
    assert _counters("round.work_ms")["van.send"] == pytest.approx(25)
    assert _counters("round.spans")["trainer.step"] == 2


def test_a_process_without_a_trainer_keeps_no_round(clock):
    """A server of a deployment of many processes judges no step: its
    spans are numbers a thread adds to and nothing a round, the
    snapshot brings the counters up, and no record ever comes."""
    for rid in range(1, 5001):
        with profiler.scope("server.push", node="l8p0", round=rid):
            with profiler.scope("trainer.fetch", node="l8p0", chunk=0):
                clock.run(1, 1)     # no round of its own, none above
    with profiler.annotate("van.send", node="l8p0", round=-1):
        clock.run(1, 1)
    (mine,) = profiler._accounts
    assert mine.rounds == {} and mine.unplaced == [] and mine.stack == []
    snap = telemetry.snapshot()
    assert snap["slow_rounds"] == [] and not profiler._host_rounds
    assert _counters("round.spans") == {
        "server.push": 5000, "trainer.fetch": 5000, "van.send": 1}
    assert _counters("round.work_ms")["trainer.fetch"] == pytest.approx(5000)
    assert set(mine.totals) == set(mine.booked) == {
        "server.push", "trainer.fetch", "van.send"}


@pytest.mark.time_limit(60)
def test_no_line_is_lost_between_closing_threads_and_the_merge():
    """More threads than cores close spans while another merges as fast
    as it can, the interpreter switching threads every few bytecodes:
    every span is in the counters once."""
    telemetry.enable(True)
    threads, spans = 24, 400
    go, stop = threading.Barrier(threads + 1), threading.Event()

    def close_spans(t):
        go.wait(30)
        for i in range(spans):
            with profiler.scope("server.select", node=f"l8p{t}",
                                round=1 + i // 100):
                with profiler.annotate("van.send", node=f"l8p{t}",
                                       round=1 + i // 100):
                    pass

    def merge():
        go.wait(30)
        while not stop.is_set():
            profiler.merge_rounds()

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=close_spans, args=(t,))
                   for t in range(threads)]
        merger = threading.Thread(target=merge)
        for t in workers + [merger]:
            t.start()
        for t in workers:
            t.join(50)
        stop.set()
        merger.join(10)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in workers + [merger])
    assert _counters("round.spans") == {
        "server.select": threads * spans, "van.send": threads * spans}
    # the threads are gone and took their accounts with them
    profiler.merge_rounds()
    assert all(a.thread.is_alive() for a in profiler._accounts)


def test_the_slow_round_rule(clock, caplog):
    slow = lambda: telemetry.snapshot()["slow_rounds"]      # noqa: E731
    events = lambda: telemetry.snapshot()["counters"].get(  # noqa: E731
        "event.round.slow", 0)
    me = threading.current_thread().name
    with caplog.at_level(logging.WARNING, logger="geomx_tpu.rounds"):
        for rid in range(1, 8):
            _step(clock, rid, 100 if rid == 3 else 10)
        assert slow() == [] and events() == 0   # seven seen: never
        # the eighth: the first seven are held to the median of eight,
        # with what their rounds held when they closed
        _step(clock, 8, 10)
        (early,) = slow()
        assert events() == 1 and len(caplog.records) == 1
        assert (early["round"], early["ms"]) == (3, pytest.approx(100))
        assert early["median_ms"] == pytest.approx(10)
        assert early["spans"]["trainer.unpack"]["longest"] == {
            "ms": 99.0, "cpu_ms": 0.0, "thread": me, "chunk": 0}
        _step(clock, 9, 21)             # 2.1 x the median
        rec, _ = sorted(slow(), key=lambda r: r["ms"])
        assert events() == 2 and len(caplog.records) == 2
        assert json.loads(caplog.records[1].getMessage().split(" ", 2)[2]) \
            == rec
        assert (rec["round"], rec["node"]) == (9, "l9p0")
        assert rec["ms"] == pytest.approx(21)
        assert rec["median_ms"] == pytest.approx(10)
        assert rec["spans"]["trainer.unpack"] == {
            "n": 1, "ms": 20.0, "cpu_ms": 0.0, "longest": {
                "ms": 20.0, "cpu_ms": 0.0, "thread": me, "chunk": 0}}
        _step(clock, 10, 20)            # 2.0 x: not over
        assert len(slow()) == 2
        # the list holds eight, the slowest
        rid = 10
        for k in range(12):
            for _ in range(3):
                rid += 1
                _step(clock, rid, 10)
            rid += 1
            _step(clock, rid, 50 + k)
        assert [r["ms"] for r in slow()] == [pytest.approx(100)] + [
            pytest.approx(50 + k) for k in range(11, 4, -1)]
        assert events() == 14 and len(caplog.records) == 14
    # a thread lets go of the rounds that were judged
    (mine,) = profiler._accounts
    assert set(mine.rounds) <= {rid - 1, rid}


def test_a_step_in_chunks_keeps_every_chunk_until_it_is_judged(clock):
    """A step takes one id a chunk: the record of a slow one holds all
    six, the other node's spans of each among them."""
    def served(first):
        for ci in range(6):
            with profiler.scope("server.push", node="l8p0",
                                round=first + ci):
                clock.run(2, 2)

    for k in range(9):
        served(1 + 6 * k)
        _step(clock, 1 + 6 * k, 10, chunks=6)
    served(55)
    _step(clock, 55, 30, chunks=6)
    served(61)
    _step(clock, 61, 10, chunks=6)      # the next step closes: 55 is judged
    (rec,) = telemetry.snapshot()["slow_rounds"]
    assert (rec["round"], rec["ms"]) == (55, pytest.approx(30))
    unpack = rec["spans"]["trainer.unpack"]
    assert unpack["n"] == 6 and unpack["longest"]["chunk"] == 5
    assert rec["others"]["l8p0"]["server.push"]["n"] == 6
    assert rec["others"]["l8p0"]["server.push"]["ms"] == pytest.approx(12)


def test_a_collection_is_booked_beside_the_span_it_ran_in():
    telemetry.enable(True)
    me = threading.current_thread().name
    for rid in range(1, 9):
        _step(_RealClocks, rid, 0)

    def collect():
        junk = [[i] for i in range(200_000)]
        junk.append(junk)               # a cycle: work for the collector
        del junk
        gc.collect()
        time.sleep(0.02)

    _step(_RealClocks, 9, 0, inside=collect)
    snap = telemetry.snapshot()
    assert snap["counters"]["host.gc_collections{gen=2}"] >= 1
    assert snap["counters"]["host.gc_ms{gen=2}"] > 0
    # (on the real clocks a step of microseconds may read slow too)
    (rec,) = [r for r in snap["slow_rounds"] if r["round"] == 9]
    assert rec["gc_threads"] == [me]
    assert rec["gc_ms"]["2"] > 0 and rec["jax_ms"] == {}
    # the collector's time stays in the span it ran in
    unpack = rec["spans"]["trainer.unpack"]
    assert unpack["ms"] >= rec["gc_ms"]["2"] + 20
    assert unpack["longest"]["thread"] == me


@pytest.mark.parametrize("on", [True, False])
def test_a_settled_heap_says_so_and_its_collections_are_still_booked(on):
    """``runtime.settle_heap``: a gauge and a counter while telemetry is
    on, nothing otherwise; its own full collection and one made after
    the freeze are both the account's, under generation 2."""
    from geomx_tpu import runtime

    telemetry.enable(on)
    with profiler.scope("trainer.step", node="w9", round=1):
        frozen = runtime.settle_heap()
        assert frozen >= gc.get_freeze_count() > 0.99 * frozen
        gc.collect()                    # walks what was made since
    snap = telemetry.snapshot()         # (conftest thaws the heap after)
    if not on:
        assert "host.gc_frozen_objects" not in snap["gauges"]
        assert not [k for k in snap["counters"] if k.startswith("host.gc")]
        return
    assert snap["gauges"]["host.gc_frozen_objects"] == frozen
    assert snap["counters"]["host.gc_freezes"] == 1
    assert snap["counters"]["host.gc_collections{gen=2}"] == 2
    assert snap["counters"]["host.gc_ms{gen=2}"] > 0


def test_the_on_path_fits_a_round_of_cell_one():
    """Telemetry on, no trace of either kind: the account of one round
    of the GPT-2 cells costs under 1.5 ms of host time summed over the
    threads, of a round of 620 ms and up. The best of several batches."""
    import jax  # noqa: F401 - loaded, as in a run

    from geomx_tpu.ps.van import Van

    van = Van.__new__(Van)
    van.is_global, van.my_id, van.root_port = False, 8, 9000
    telemetry.enable(True)
    profiler._annotation = None
    best = float("inf")
    for _ in range(40):
        t0 = time.perf_counter()
        for _ in range(SPANS_A_ROUND_CELL_1):
            # the arguments built a span, as the sites do
            with profiler.scope("server.select", cat="kvstore",
                                **van.round_args(7)):
                pass
        best = min(best, time.perf_counter() - t0)
        profiler.merge_rounds()         # once a round, as a trainer does
    assert _counters("round.spans") == {
        "server.select": 40 * SPANS_A_ROUND_CELL_1}
    assert best < ON_BUDGET_S, (
        f"{SPANS_A_ROUND_CELL_1} spans of the account took "
        f"{best * 1e6:.0f} us")


def test_telemetry_is_the_accounts_one_switch(recorder):
    """Off: what the parent gave, the shared no-op or (a JAX trace
    running) the bare annotation. On: the table's spans alone."""
    assert not telemetry.enabled()
    with profiler.scope("trainer.step", round=1) as span:
        assert type(span) is recorder
    with profiler.annotate("van.send", round=1) as span:
        assert type(span) is recorder
    profiler._annotation = None
    assert profiler.scope("trainer.step", round=1) is profiler._NO_SPAN
    assert profiler.annotate("van.send", round=1) is profiler._NO_SPAN
    telemetry.enable(True)
    assert profiler.scope("update:key3") is profiler._NO_SPAN
    with profiler.scope("trainer.step", round=1):
        pass
    telemetry.enable(False)
    assert profiler.scope("trainer.step", round=1) is profiler._NO_SPAN
    assert _counters("round.spans") == {"trainer.step": 1}


def test_the_account_without_jax_imports_nothing(monkeypatch):
    monkeypatch.setattr(profiler, "_annotation", None)
    for name in [m for m in sys.modules
                 if m == "jax" or m.startswith("jax.")]:
        monkeypatch.delitem(sys.modules, name)
    telemetry.enable(True)
    with profiler.scope("server.push", node="l8p0", round=3) as span:
        span.set_metadata(round=4)
        gc.collect()
    snap = telemetry.snapshot()
    assert snap["counters"]["round.spans{span=server.push}"] == 1
    assert snap["counters"]["host.gc_collections{gen=2}"] == 1
    assert "jax" not in sys.modules and profiler._annotation is None
    assert not profiler._jax_watched


def test_an_untraced_round_fills_the_account_and_a_slow_one_names_itself(
        caplog):
    """Telemetry on, neither trace running: every span the rounds open
    is in all its counter families, and a round that sleeps inside
    ``trainer.unpack`` on one worker leaves one record that says so,
    with both workers' and the servers' spans under its one id."""
    from geomx_tpu.trainer_device import DeviceResidentTrainer

    telemetry.enable(True)
    naps = []

    def rounds(tr, X, first, meet):
        for _ in range(9):
            tr.step(X, None)
            meet.wait(60)
        if first:
            chunk_up = tr._chunk_up

            def slow_chunk_up(ci, agg):
                naps.append(time.sleep(0.5))
                return chunk_up(ci, agg)

            tr._chunk_up = slow_chunk_up
        tr.step(X, None)
        meet.wait(60)

    assert DeviceResidentTrainer._chunk_up       # what the nap stands in
    with caplog.at_level(logging.WARNING, logger="geomx_tpu.rounds"):
        _two_party_rounds(rounds)
        snap = telemetry.snapshot()
    assert len(naps) == 1 and not profiler.is_running()
    table = {s.name: s.cls for s in profiler.ROUND_SPANS}
    closed = _counters("round.spans")
    assert set(table) - set(closed) == {"link.hold"}   # no shaped link
    work = {n for n in closed if table[n] == "work"}
    assert set(_counters("round.work_ms")) == work
    assert set(_counters("round.work_cpu_ms")) == work
    assert set(_counters("round.wait_ms")) == {"trainer.wait"}
    assert closed["trainer.step"] == 22      # 11 steps a worker
    # the round of the nap; the step that compiled, held to the median
    # of the first eight, says so of itself (``jax_ms``), and on a busy
    # box a round of 25 ms may read slow too
    (rec,) = [r for r in snap["slow_rounds"] if "trainer.unpack" in
              r["spans"] and r["spans"]["trainer.unpack"]["ms"] >= 500]
    assert snap["counters"]["event.round.slow"] == len(caplog.records) \
        >= len(snap["slow_rounds"]) >= 1
    assert rec["ms"] > 500 > 2 * rec["median_ms"]
    longest = max(rec["spans"].items(),
                  key=lambda kv: kv[1]["longest"]["ms"])
    assert longest[0] == "trainer.unpack"
    assert longest[1]["longest"]["ms"] >= 500
    assert longest[1]["longest"]["cpu_ms"] < 50
    assert {"trainer.step", "trainer.fetch", "trainer.pack", "trainer.wait",
            "trainer.h2d", "trainer.apply", "pipeline:send",
            "pipeline:recv", "van.send", "van.recv"} <= set(rec["spans"])
    # one id across the process: the other worker, two party servers and
    # the global server booked their spans of this round under it
    others = rec["others"]
    assert len(others) >= 4 and rec["node"] not in others
    seen = set().union(*(set(spans) for spans in others.values()))
    assert {"trainer.step", "server.push", "server.select",
            "server.forward", "server.push.global", "server.respond",
            "server.pullback", "van.recv"} <= seen
