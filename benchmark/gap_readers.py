"""Whose time the chip's idle gaps are: the readers of ``gap.*`` and
``server.offcpu_share``.

The program opens its ROUND SPANS (``geomx_tpu.profiler.ROUND_SPANS``:
name, layer, class ``work`` or ``wait``) as ``TraceAnnotation``s, so in
a ``--trace 1`` run they lie on their threads' lines of the host planes,
on the clock of the chip's ``XLA Ops`` line. From the run's own
``.xplane.pb``:

- the first chip's operations and the traced window, as
  ``trace_reduce.reduce`` takes them, and the window's gaps longer than
  :data:`MIN_GAP_NS`;
- on each host thread the INNERMOST open span owns an instant (a span's
  time is its self time);
- each instant of a gap goes, in equal parts, to the buckets that own a
  ``work`` span on some thread at that instant; where none does, to
  ``link`` if a ``wait`` span of the link's layer is open, else to
  ``unnamed``. The parts add up to the gaps.

A bucket is a metric: ``layer_metrics/gap.<bucket>_ms.json`` lists the
span names it reads under ``spans``. A program without the table (a
parent commit) or a trace without its spans gives nothing to read, and
the metrics are left out.

    python -m benchmark.gap_readers <trace dir or .xplane.pb> [rounds]

prints the split of one trace and its longest unnamed stretches with
the spans that end and start around them.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

from benchmark import manifest, trace_reduce
from benchmark.qwen3next_readers import _xplane_of
from benchmark.readers import Context, _counter_sum

MIN_GAP_NS = 5_000_000
UNNAMED = "unnamed"
LINK = "link"

Segment = Tuple[int, int, str, str]     # start, end, span name, class


def span_table() -> Dict[str, Tuple[str, str]]:
    """name -> (layer, class) of the program's round spans; empty where
    the program exports none."""
    from geomx_tpu import profiler

    return {s.name: (s.layer, s.cls)
            for s in getattr(profiler, "ROUND_SPANS", ())}


@functools.lru_cache(maxsize=1)
def bucket_of_span() -> Dict[str, str]:
    """span name -> the bucket whose metric file lists it."""
    out = {}
    for path in sorted(glob.glob(os.path.join(
            manifest.BENCH_DIR, "layer_metrics", "gap.*.json"))):
        with open(path) as f:
            spec = json.load(f)
        for name in spec.get("spans", []):
            out[name] = spec["bucket"]
    return out


def self_segments(events: List[Tuple[int, int, str, str]]) -> List[Segment]:
    """One thread's spans (start, end, name, class), nested as a thread's
    are, cut into the stretches each OWNS: where a child is open the
    parent is not."""
    out: List[Segment] = []
    stack: List[list] = []      # [end, name, class, owned from]

    def close(upto: int) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, cls, since = stack.pop()
            if end > since:
                out.append((since, end, name, cls))
            if stack:
                stack[-1][3] = max(stack[-1][3], end)

    for s, e, name, cls in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            top = stack[-1]
            if s > top[3]:
                out.append((top[3], s, top[1], top[2]))
            e = min(e, top[0])      # a child ends with its parent
            top[3] = max(top[3], s)
        if e > s:
            stack.append([e, name, cls, s])
    close(max((ev[1] for ev in events), default=0))
    return out


def attribute(gaps: List[Tuple[int, int]], threads: List[List[Segment]],
              bucket_of: Dict[str, str]) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` by bucket (``unnamed`` included), from the
    threads' owned stretches. A ``wait`` stretch counts only through the
    ``link`` bucket, and only where no ``work`` stretch is open."""
    marks = []      # (time, +1/-1, bucket, is work)
    for segs in threads:
        for s, e, name, cls in segs:
            bucket = bucket_of.get(name)
            if cls == "work" and bucket is not None:
                marks += [(s, 1, bucket, True), (e, -1, bucket, True)]
            elif cls == "wait" and bucket == LINK:
                marks += [(s, 1, LINK, False), (e, -1, LINK, False)]
    for s, e in gaps:
        marks += [(s, 0, "", False), (e, 0, "", False)]
    marks.sort(key=lambda m: m[0])
    out: Dict[str, float] = {UNNAMED: 0.0}
    work: Dict[str, int] = {}
    holds = 0
    gi, prev = 0, None
    gaps = sorted(gaps)
    for t, step, bucket, is_work in marks:
        if prev is not None and t > prev:
            while gi < len(gaps) and gaps[gi][1] <= prev:
                gi += 1
            if gi < len(gaps) and gaps[gi][0] <= prev:
                # (prev, t) lies in one gap: the gaps' ends are marks
                live = [b for b, n in work.items() if n > 0]
                if live:
                    for b in live:
                        out[b] = out.get(b, 0.0) + (t - prev) / len(live)
                else:
                    b = LINK if holds > 0 else UNNAMED
                    out[b] = out.get(b, 0.0) + (t - prev)
        if step:
            if is_work:
                work[bucket] = work.get(bucket, 0) + step
            else:
                holds += step
        prev = t
    return out


def window_and_gaps(pd) -> Optional[Tuple[int, int, List[Tuple[int, int]]]]:
    """The traced window and the first chip's gaps in it, as
    ``trace_reduce.reduce`` computes them (every chip's operations and
    the harness's spans bound the window)."""
    device, lo, hi = {}, [], []
    for plane in pd.planes:
        m = trace_reduce.DEVICE_RE.match(plane.name)
        for line in plane.lines:
            if m and line.name == trace_reduce.OPS_LINE:
                device[int(m.group(1))] = [
                    (int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events]
            elif not m:
                for e in line.events:
                    if e.name.startswith(trace_reduce.SPAN_PREFIX):
                        lo.append(int(e.start_ns))
                        hi.append(int(e.start_ns + e.duration_ns))
    used = {d: iv for d, iv in device.items() if iv}
    if not used:
        return None
    for iv in used.values():
        lo.append(min(s for s, _ in iv))
        hi.append(max(e for _, e in iv))
    return min(lo), max(hi), trace_reduce.gaps(used[min(used)],
                                               min(lo), max(hi))


def host_threads(pd, table: Dict[str, Tuple[str, str]]
                 ) -> List[List[Tuple[int, int, str, str]]]:
    """The table's spans of every host thread (one line each)."""
    out = []
    for plane in pd.planes:
        if trace_reduce.DEVICE_RE.match(plane.name):
            continue
        for line in plane.lines:
            evs = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                    e.name, table[e.name][1])
                   for e in line.events if e.name in table]
            if evs:
                out.append(evs)
    return out


def split(pd) -> Optional[dict]:
    """``{"gaps_ns": ..., "by_bucket": {bucket: ns}, "gaps": [...],
    "threads": [...]}`` of one trace; nothing where the program exports
    no table, the trace holds none of its spans, or no chip ran."""
    table = span_table()
    found = window_and_gaps(pd) if table else None
    if found is None:
        return None
    lo, hi, all_gaps = found
    long_gaps = [g for g in all_gaps if g[1] - g[0] > MIN_GAP_NS]
    threads = [self_segments(evs) for evs in host_threads(pd, table)]
    if not threads:
        return None
    return {"gaps_ns": sum(e - s for s, e in long_gaps), "gaps": long_gaps,
            "by_bucket": attribute(long_gaps, threads, bucket_of_span()),
            "threads": threads, "window": (lo, hi)}


@functools.lru_cache(maxsize=1)
def _split_of(path: str, _mtime: float) -> Optional[dict]:
    import jax

    return split(jax.profiler.ProfileData.from_file(path))


def _run_split(ctx: Context) -> Optional[dict]:
    """The split of this run's trace, parsed once a run."""
    path = _xplane_of(ctx) if ctx.trace is not None else None
    return _split_of(path, os.path.getmtime(path)) if path else None


def gap_bucket_ms(ctx: Context, spec: dict):
    """Milliseconds a traced round of the chip's gaps over 5 ms that fall
    to ``spec["bucket"]``."""
    got = _run_split(ctx)
    if got is None or not got["gaps_ns"]:
        return None
    return got["by_bucket"].get(spec["bucket"], 0.0) / 1e6 / ctx.trace["rounds"]


def gap_named_share(ctx: Context, spec: dict):
    """100 x (1 - unnamed / the gaps' length), %."""
    got = _run_split(ctx)
    if got is None or not got["gaps_ns"]:
        return None
    return 100.0 * (1.0 - got["by_bucket"][UNNAMED] / got["gaps_ns"])


def offcpu_share(ctx: Context, spec: dict):
    """100 x (1 - window delta of the ``cpu`` counters / of the ``wall``
    counters), %: the share of the servers' timed numpy intervals that
    their threads spent off the processor."""
    if len(ctx.snaps) < 2:
        return None

    def delta(prefixes):
        return sum(_counter_sum(ctx.snaps[-1], p)
                   - _counter_sum(ctx.snaps[0], p) for p in prefixes)

    wall, cpu = delta(spec["wall"]), delta(spec["cpu"])
    if not wall or not cpu:
        return None
    return 100.0 * (1.0 - cpu / wall)


# -- by hand -------------------------------------------------------------------

def unnamed_stretches(got: dict, top: int = 8) -> List[dict]:
    """The longest stretches of the gaps in which no thread owns a
    ``work`` span and no link holds a frame, each with the last span to
    end before it and the first to start after it."""
    bucket_of = bucket_of_span()
    busy, edges = [], []
    for segs in got["threads"]:
        for s, e, name, cls in segs:
            if (cls == "work" and name in bucket_of) or (
                    cls == "wait" and bucket_of.get(name) == LINK):
                busy.append((s, e))
                edges.append((s, e, name))
    out = []
    for g_lo, g_hi in got["gaps"]:
        for s, e in trace_reduce.gaps(busy, g_lo, g_hi):
            before = max((x for x in edges if x[1] <= s),
                         key=lambda x: x[1], default=None)
            after = min((x for x in edges if x[0] >= e),
                        key=lambda x: x[0], default=None)
            out.append({"ms": (e - s) / 1e6,
                        "at_ms": (s - got["window"][0]) / 1e6,
                        "after": before[2] if before else None,
                        "before": after[2] if after else None})
    return sorted(out, key=lambda u: -u["ms"])[:top]


def main(argv: List[str]) -> int:
    import jax

    path = argv[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    rounds = int(argv[1]) if len(argv) > 1 else 1
    got = split(jax.profiler.ProfileData.from_file(path))
    if got is None:
        print("no round spans or no device operations in", path)
        return 1
    print(json.dumps({
        "gaps_ms": [round((e - s) / 1e6, 3) for s, e in got["gaps"]],
        "ms_per_round": {b: round(ns / 1e6 / rounds, 3)
                         for b, ns in sorted(got["by_bucket"].items())},
        "named_share": round(100 * (1 - got["by_bucket"][UNNAMED]
                                    / max(got["gaps_ns"], 1)), 2),
        "unnamed_stretches": unnamed_stretches(got)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
