"""The general readers of per-layer metrics.

A per-layer metric is ``benchmark/layer_metrics/<name>.json``; its
``reader`` names a function here (or ``module:function`` in a module a
later PR adds under ``benchmark/``). A reader takes the run's
:class:`Context` and the metric's own file, and returns a number, or
``None`` when it finds nothing to read: the harness then leaves the
metric out of the line.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import numpy as np


@dataclasses.dataclass
class Context:
    cell: str
    chips: int
    peaks: Optional[dict]       # peaks.json row of this device
    rounds: int                 # rounds in the window
    timed: List[dict]           # worker 0's step_timed() phase dicts
    snaps: List[dict]           # telemetry snapshot at every boundary
    trace: Optional[dict]       # trace_reduce.reduce_dir() or None
    tokens_traced: int          # tokens of the traced step() rounds
    reference: Any              # the family's references/<family>.py
    cfg: dict                   # the configuration as it is run
    seq_len: int


def step_timed_median(ctx: Context, spec: dict):
    vals = [t[spec["key"]] for t in ctx.timed if spec["key"] in t]
    return float(np.median(vals)) if vals else None


def busy_mfu(ctx: Context, spec: dict):
    """The operations the model's forward and backward passes require
    for the tokens of the traced rounds, over what the chips could have
    done in the seconds the trace shows them BUSY, in %: how well the
    device step (forward, backward, select, apply) uses the time it
    holds the chip, whatever the host does between. The count is the
    family's own: ``references/<family>.train_flops_per_token``."""
    count = getattr(ctx.reference, "train_flops_per_token", None)
    if ctx.peaks is None or ctx.trace is None or count is None:
        return None
    return (100.0 * count(ctx.cfg, ctx.seq_len) * ctx.tokens_traced
            / (ctx.trace["busy_s"] * ctx.chips
               * ctx.peaks["bf16_flops_per_s"]))


def _counter_sum(snap: dict, prefix: str, must_contain: str = "") -> float:
    return sum(v for k, v in snap.get("counters", {}).items()
               if k.startswith(prefix) and must_contain in k)


def counter_per_round(ctx: Context, spec: dict):
    """Window delta of every counter whose key starts with ``prefix``
    (and contains ``must_contain``), over the window's rounds."""
    if len(ctx.snaps) < 2 or not ctx.rounds:
        return None
    args = (spec["prefix"], spec.get("must_contain", ""))
    delta = _counter_sum(ctx.snaps[-1], *args) - _counter_sum(
        ctx.snaps[0], *args)
    return delta / ctx.rounds if delta else None


def gauge_sum_mean(ctx: Context, spec: dict):
    """Mean over the round boundaries of the sum of the matching
    gauges."""
    sums = []
    for snap in ctx.snaps[1:]:
        vals = [v for k, v in snap.get("gauges", {}).items()
                if k.startswith(spec["prefix"])
                and spec.get("must_contain", "") in k]
        if vals:
            sums.append(sum(vals))
    return float(np.mean(sums)) if sums else None


def trace_idle_share(ctx: Context, spec: dict):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def trace_op_ms_per_round(ctx: Context, spec: dict):
    """Summed device time of the operations whose name starts with one
    of ``prefix``, on the first chip, per traced round."""
    if ctx.trace is None:
        return None
    total = sum(s for name, s in ctx.trace["op_seconds_first_chip"].items()
                if any(name.startswith(p) for p in spec["prefix"]))
    return 1e3 * total / ctx.trace["rounds"] if total else None
