"""The gap readers: the attribution arithmetic on hand-made planes, the
metric files against the program's table, and a small trace of real
two-party rounds recorded on the chip (``record_rounds.py``) with the
readers' numbers for it pinned."""

import glob
import json
import os
import types

import pytest

from benchmark import gap_readers as gr, manifest
from benchmark.readers import Context

MS = 1_000_000
DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(name, start_ms, end_ms):
    return types.SimpleNamespace(name=name, start_ns=float(start_ms * MS),
                                 duration_ns=float((end_ms - start_ms) * MS))


def trace(ops, *threads, steps=((0, 100),)):
    """A ProfileData look-alike: one chip whose ``XLA Ops`` line holds
    ``ops``, the harness's spans on a thread of their own, and one host
    line a thread."""
    line = lambda name, events: types.SimpleNamespace(      # noqa: E731
        name=name, events=events)
    device = types.SimpleNamespace(
        name="/device:TPU:0",
        lines=[line("XLA Ops", [ev("%fusion.1 = f32[] fusion()", s, e)
                                for s, e in ops]),
               line("Steps", [ev("1", 0, 100)])])
    host = types.SimpleNamespace(
        name="/host:CPU",
        lines=[line("python", [ev(f"bench.step w0 r{i}", s, e)
                               for i, (s, e) in enumerate(steps)])]
        + [line("python", list(t)) for t in threads])
    return types.SimpleNamespace(planes=[device, host])


def ms(split):
    return {b: ns / MS for b, ns in split["by_bucket"].items() if ns}


# device busy 0-10 and 90-100: one gap of 80 ms, 10..90
OPS = [(0, 10), (90, 100)]


def test_nesting_on_one_thread_gives_the_parent_its_self_time():
    got = gr.split(trace(OPS, [ev("server.push", 10, 50),
                               ev("server.forward", 20, 40),
                               ev("server.select", 25, 35)]))
    assert got["gaps"] == [(10 * MS, 90 * MS)]
    # push owns 10-20 and 40-50, forward 20-25 and 35-40: one bucket;
    # select owns 25-35; nobody 50-90
    assert ms(got) == {"party_server": 30, "select": 10, "unnamed": 40}


def test_two_layers_at_once_share_the_instant_evenly():
    got = gr.split(trace(OPS, [ev("server.select", 10, 50)],
                         [ev("server.select", 10, 30)],
                         [ev("trainer.unpack", 20, 50),
                          ev("trainer.wait", 50, 90)],
                         [ev("server.push.global", 40, 50)]))
    # 10-20 select (two threads, one layer); 20-40 select + trainer;
    # 40-50 select + trainer + global server; a wait span owns nothing
    assert ms(got) == pytest.approx({
        "select": 10 + 10 + 10 / 3, "trainer": 10 + 10 / 3,
        "global_server": 10 / 3, "unnamed": 40})
    assert sum(got["by_bucket"].values()) == pytest.approx(got["gaps_ns"])


def test_a_wait_span_alone_is_unnamed_and_a_link_hold_is_the_links():
    got = gr.split(trace(OPS, [ev("trainer.step", 0, 100),
                               ev("trainer.wait", 10, 90)],
                         [ev("link.hold", 30, 70)],
                         [ev("van.recv", 60, 80)]))
    # the step's own time lies outside the gap; the wait inside it names
    # nobody; the hold counts while no work span is open (30-60)
    assert ms(got) == {"unnamed": 30, "link": 30, "van": 20}


def test_a_gap_under_five_ms_is_ignored_and_the_parts_sum_to_the_gaps():
    ops = [(0, 10), (14, 50), (56, 100)]        # gaps of 4 and of 6 ms
    got = gr.split(trace(ops, [ev("pipeline:recv", 9, 15),
                               ev("van.send", 52, 58)]))
    assert got["gaps"] == [(50 * MS, 56 * MS)]
    assert ms(got) == {"unnamed": 2, "van": 4}
    assert sum(got["by_bucket"].values()) == got["gaps_ns"] == 6 * MS


def test_the_window_is_trace_reduces():
    """The harness's spans and every operation bound the window: a step
    that starts before the first operation opens a gap."""
    from benchmark import trace_reduce

    pd = trace([(20, 90)], [ev("trainer.step", 5, 95)], steps=((5, 95),))
    got = gr.split(pd)
    reduced = trace_reduce.reduce(pd, chips=1, rounds=1)
    assert got["window"] == (5 * MS, 95 * MS)
    # 5-20 counts; 90-95 is not over 5 ms
    assert sorted((e - s) / 1e9 for s, e in got["gaps"]) == sorted(
        t for _label, t in reduced["idle_gaps"] if t > 0.005)
    assert ms(got) == {"trainer": 15}


def test_nothing_to_read_gives_nothing(monkeypatch):
    pd = trace(OPS, [ev("not.a.round.span", 10, 90)])
    assert gr.split(pd) is None             # none of the table's spans
    host_only = types.SimpleNamespace(planes=trace(OPS).planes[1:])
    assert gr.split(host_only) is None      # no chip ran
    # a parent commit: the program exports no table
    from geomx_tpu import profiler
    monkeypatch.delattr(profiler, "ROUND_SPANS")
    assert gr.span_table() == {}
    assert gr.split(trace(OPS, [ev("server.select", 10, 90)])) is None
    ctx = Context(cell="gpt2s-hips-bsc", chips=1, peaks=None, rounds=3,
                  timed=[], snaps=[], trace=None, tokens_traced=0,
                  reference=None, cfg={}, seq_len=0)
    for name in ("gap.named_share", "gap.select_ms", "server.offcpu_share"):
        spec = manifest.layer_metric_spec(name)
        assert manifest.resolve(spec["reader"])(ctx, spec) is None


def test_offcpu_share_reads_the_window_delta_of_both_clocks():
    def snap(sel, sel_cpu, agg, agg_cpu):
        return {"counters": {
            "server.bsc_select_ms{tier=global}": sel,
            "server.bsc_select_cpu_ms{tier=global}": sel_cpu,
            "server.aggregate_ms{tier=local}": agg,
            "server.aggregate_cpu_ms{tier=local}": agg_cpu}}

    ctx = Context(cell="c", chips=1, peaks=None, rounds=2, timed=[],
                  snaps=[snap(100, 80, 10, 10), snap(700, 400, 110, 60)],
                  trace=None, tokens_traced=0, reference=None, cfg={},
                  seq_len=0)
    spec = manifest.layer_metric_spec("server.offcpu_share")
    # wall 600 + 100, cpu 320 + 50
    assert gr.offcpu_share(ctx, spec) == pytest.approx(
        100 * (1 - 370 / 700))
    # the wall counters' prefixes do not take the cpu counters in
    from benchmark import readers
    assert readers._counter_sum(ctx.snaps[1], "server.aggregate_ms") == 110


# ---------------------------------------------------------------------------
# the metric files against the program's table


def _gap_files():
    out = {}
    for path in sorted(glob.glob(os.path.join(
            manifest.BENCH_DIR, "layer_metrics", "gap.*.json"))):
        with open(path) as f:
            spec = json.load(f)
        out[spec["name"]] = spec
    return out


def test_every_work_span_is_read_by_exactly_one_metric():
    from geomx_tpu import profiler

    table = {s.name: s for s in profiler.ROUND_SPANS}
    listed = [n for spec in _gap_files().values()
              for n in spec.get("spans", [])]
    assert len(listed) == len(set(listed))
    assert set(listed) <= set(table)            # no name the program lacks
    work = {n for n, s in table.items() if s.cls == "work"}
    assert work <= set(listed)
    # what is listed beside them waits, and only the link's wait is read
    assert set(listed) - work == {"link.hold"}
    for spec in _gap_files().values():
        for n in spec.get("spans", []):
            assert table[n].layer == spec["bucket"]


def test_the_eight_metrics_are_in_the_manifest_as_the_issue_names_them():
    man = manifest.load()
    by_name = {m["name"]: m for m in man["per_layer"]}
    names = ["gap.named_share", "gap.trainer_ms", "gap.van_ms",
             "gap.party_server_ms", "gap.select_ms", "gap.global_server_ms",
             "gap.link_ms", "server.offcpu_share"]
    assert [m["name"] for m in man["per_layer"]][-8:] == names
    for n in names:
        m = by_name[n]
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == ("program_counter"
                               if n == "server.offcpu_share"
                               else "program_span")
        assert m.get("workloads") == (["gpt2s-hips-bsc-wan100"]
                                      if n == "gap.link_ms" else None)
    assert by_name["gap.named_share"]["better"] == "higher"
    assert set(_gap_files()) == set(names[:-1])


# ---------------------------------------------------------------------------
# a recorded trace of real rounds (benchmark/tests/record_rounds.py)

ROUNDS_TRACE = os.path.join(DATA, "gpt2_rehearsal_rounds.xplane.pb")


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_the_recorded_rounds_read_as_pinned():
    import jax

    with open(os.path.join(DATA, "gpt2_rehearsal_rounds.json")) as f:
        pinned = json.load(f)
    assert os.path.getsize(ROUNDS_TRACE) < 200_000
    got = gr.split(jax.profiler.ProfileData.from_file(ROUNDS_TRACE))
    assert [e - s for s, e in got["gaps"]] == pinned["gaps_ns"]
    assert {b: round(ns, 3) for b, ns in got["by_bucket"].items()
            } == pinned["by_bucket_ns"]
    assert sum(got["by_bucket"].values()) == pytest.approx(
        got["gaps_ns"], abs=1)
    # three rounds, every layer of the table at work in the gaps
    assert set(pinned["by_bucket_ns"]) >= {
        "trainer", "van", "party_server", "select", "global_server",
        "unnamed"}
    table = gr.span_table()
    seen = {name for segs in got["threads"] for _s, _e, name, _c in segs}
    assert {n for n, (_l, cls) in table.items() if cls == "work"} - seen \
        <= {"server.pull", "server.pull.global"}
