"""From a profiler trace (``.xplane.pb``) to numbers. Kept with the
benchmark so that every PR computes them in the same way.

A TPU trace has one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Ops`` holds one event per executed HLO operation (start and
duration in nanoseconds on the trace's own clock); the host's threads
are lines of the plane ``/host:CPU``, where the harness's own
``TraceAnnotation`` spans (``bench.step w<worker> r<round>``) land.

- busy: the union of the ``XLA Ops`` intervals of a chip, clipped to the
  window; averaged over the chips in use. The window runs from the
  start of the first ``bench.step`` span to the end of the last.
- idle gaps: the longest intervals with no operation on the first chip,
  each labelled by what the harness's host spans say the workers were
  doing in it.
- device_ops: summed duration by operation name, all chips, largest
  first.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench.step"
TOP = 10


class NoDeviceOps(RuntimeError):
    pass


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line,
    ``%sort.3 = (bf16[...]) sort(...)``: keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _intervals(line) -> List[Tuple[int, int, str]]:
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns),
             op_name(e.name)) for e in line.events]


def union_length(iv: List[Tuple[int, int]], lo: int, hi: int) -> int:
    """Length of the union of ``iv`` clipped to [lo, hi]."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in iv):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(iv: List[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The maximal intervals of [lo, hi] that ``iv`` leaves uncovered."""
    out, cur = [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in iv):
        if e <= s:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def _label(gap: Tuple[int, int], spans: List[Tuple[int, int, str]]) -> str:
    """What the workers were doing over most of the gap: the harness's
    spans that overlap it, by overlap; ``between rounds`` where none."""
    s, e = gap
    over = defaultdict(int)
    for a, b, name in spans:
        o = min(e, b) - max(s, a)
        if o > 0:
            over[name] += o
    if not over:
        return "between rounds"
    best = sorted(over.items(), key=lambda kv: -kv[1])
    workers = sorted({re.sub(r" r-?\d+$", "", n.replace(SPAN_PREFIX, "")
                             ).strip() for n, _ in best})
    return "inside step of " + "+".join(workers)


def reduce(pd, chips: int, rounds: int) -> dict:
    """``pd``: a ``jax.profiler.ProfileData``."""
    device_lines, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_RE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_lines[int(m.group(1))] = _intervals(line)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((int(e.start_ns),
                                      int(e.start_ns + e.duration_ns),
                                      e.name))
    used = {d: iv for d, iv in device_lines.items() if iv}
    if not used:
        raise NoDeviceOps("the trace holds no operation on any "
                          f"/device:TPU plane ({OPS_LINE!r} lines)")
    # the device's clock and the host's differ by about a millisecond
    # (in the recorded probe the first operation starts 1.0 ms before
    # the host span that launched it), so the window is the spans'
    # extent widened to hold every operation
    los = [min(s for s, _, _ in iv) for iv in used.values()]
    his = [max(e for _, e, _ in iv) for iv in used.values()]
    lo = min(los + [s for s, _, _ in spans])
    hi = max(his + [e for _, e, _ in spans])
    by_name: Dict[str, int] = defaultdict(int)
    first_chip: Dict[str, int] = defaultdict(int)
    busy = []
    first = min(used)
    for d, iv in sorted(used.items()):
        busy.append(union_length([(s, e) for s, e, _ in iv], lo, hi))
        for s, e, name in iv:
            by_name[name] += e - s
            if d == first:
                first_chip[name] += e - s
    # chips the cell asked for but that ran nothing count as idle
    n_chips = max(chips, len(used))
    busy_s = sum(busy) / n_chips / 1e9
    idle = sorted(gaps([(s, e) for s, e, _ in used[first]], lo, hi),
                  key=lambda g: g[0] - g[1])[:TOP]
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_s, "window_s": (hi - lo) / 1e9, "rounds": rounds,
        "chips_with_ops": sorted(used),
        "device_ops": [[n, t / 1e9] for n, t in top],
        "idle_gaps": [[_label(g, spans), (g[1] - g[0]) / 1e9]
                      for g in idle],
        "op_seconds_first_chip": {n: t / 1e9 for n, t in first_chip.items()},
    }


def reduce_dir(trace_dir: str, chips: int, rounds: int) -> dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(find_xplane(trace_dir))
    return reduce(pd, chips, rounds)
