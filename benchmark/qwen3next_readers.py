"""Readers of the ``qwen3next`` family's per-layer metrics.

Two read the device trace BY SCOPE. ``trace_reduce`` keeps an
operation's instruction name, and the linear-attention layer's
operations are fusions, dots and a loop like any other layer's: what
tells them apart is the ``jax.named_scope`` they were traced under
(``linear_attention``, inside it ``causal_conv`` and
``gated_delta_rule``), which XLA carries as the instruction's
``op_name`` and the TPU profiler writes as the stat ``tf_op`` of the
event's metadata (``jit(fwd_chunks)/.../linear_attention/
gated_delta_rule/while/body/dot_general``; the backward pass's
operations keep the forward scope inside ``transpose(jvp(...))``).
``jax.profiler.ProfileData`` shows an event's own stats only, so the
run's ``.xplane.pb`` is read here, with the few lines of protobuf wire
format that takes. A scope's time is the UNION of its events' intervals
on the first chip: a loop is one event and its body's operations are
events inside it.

``gdn.head_tokens`` and the ``moe.*`` counters are booked once a round
through ``grad_step.counted``. A program without the scopes or the
counters (a parent commit, another family) gives nothing to read, and
the metric is left out.
"""

from __future__ import annotations

import functools
import glob
import os
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark import manifest, moe_readers, trace_reduce
from benchmark.readers import Context

SCOPE_STAT = "tf_op"


# -- the protobuf wire format, as far as an XSpace needs it -------------------
# XSpace{1: XPlane}; XPlane{2: name, 3: XLine, 4: map<id, XEventMetadata>,
# 5: map<id, XStatMetadata>}; XLine{2: name, 3: timestamp_ns, 4: XEvent};
# XEvent{1: metadata_id, 2: offset_ps, 3: duration_ps};
# XEventMetadata{1: id, 5: XStat}; XStat{1: metadata_id, 5: str_value,
# 7: ref_value}; XStatMetadata{1: id, 2: name}; a map entry is {1: key,
# 2: value}.

def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, the
    bytes of a length-delimited field; fixed-width fields are skipped."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield number, value
        elif wire == 2:
            size, pos = _varint(buf, pos)
            yield number, buf[pos:pos + size]
            pos += size
        elif wire in (1, 5):
            pos += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")


def _message(buf: bytes) -> Dict[int, list]:
    out: Dict[int, list] = {}
    for number, value in _fields(buf):
        out.setdefault(number, []).append(value)
    return out


def _first(msg: Dict[int, list], number: int, default=0):
    return msg[number][0] if number in msg else default


def scoped_ops(xspace: bytes) -> List[Tuple[int, int, str]]:
    """(start, end, ``tf_op``) in picoseconds of the ``XLA Ops`` events
    of the first chip that has any, ``tf_op`` empty where the event's
    metadata carries none."""
    planes = []
    for plane in _message(xspace).get(1, []):
        plane = _message(plane)
        m = trace_reduce.DEVICE_RE.match(_first(plane, 2, b"").decode())
        if m:
            planes.append((int(m.group(1)), plane))
    for _chip, plane in sorted(planes, key=lambda p: p[0]):
        stat_names = {}
        for entry in plane.get(5, []):
            meta = _message(_first(_message(entry), 2, b""))
            stat_names[_first(meta, 1)] = _first(meta, 2, b"").decode()
        scope_of = {}
        for entry in plane.get(4, []):
            meta = _message(_first(_message(entry), 2, b""))
            for stat in meta.get(5, []):
                stat = _message(stat)
                if stat_names.get(_first(stat, 1)) == SCOPE_STAT:
                    scope_of[_first(meta, 1)] = _first(
                        stat, 5, b"").decode() or stat_names.get(
                            _first(stat, 7), "")
        out = []
        for line in plane.get(3, []):
            line = _message(line)
            if _first(line, 2, b"").decode() != trace_reduce.OPS_LINE:
                continue
            origin = _first(line, 3) * 1000
            for event in line.get(4, []):
                # a trace has a million events: no dict an event
                meta = offset = duration = 0
                for number, value in _fields(event):
                    if number == 1:
                        meta = value
                    elif number == 2:
                        offset = value
                    elif number == 3:
                        duration = value
                out.append((origin + offset, origin + offset + duration,
                            scope_of.get(meta, "")))
        if out:
            return out
    return []


def scope_intervals(xspace: bytes, scope: str) -> List[Tuple[int, int]]:
    """(start, end) of the first chip's operations whose ``tf_op``
    names ``scope``."""
    return [(s, e) for s, e, op in scoped_ops(xspace) if scope in op]


@functools.lru_cache(maxsize=1)
def _ops_of(path: str, _mtime: float) -> List[Tuple[int, int, str]]:
    with open(path, "rb") as f:
        return scoped_ops(f.read())


def _xplane_of(ctx: Context) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        manifest.ROOT, "benchmark_out", "trace", ctx.cell + "-*", "**",
        "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def scope_ms_per_round(ctx: Context, spec: dict):
    """Device milliseconds a traced round under the ``jax.named_scope``
    ``spec["scope"]`` on the first chip; nothing where the run was not
    traced or no operation carries the scope. The file is parsed once
    a run, whatever the number of metrics that read it."""
    path = _xplane_of(ctx) if ctx.trace is not None else None
    if path is None:
        return None
    spans = [(s, e) for s, e, op in _ops_of(path, os.path.getmtime(path))
             if spec["scope"] in op]
    if not spans:
        return None
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    return trace_reduce.union_length(spans, lo, hi) / 1e9 / ctx.trace["rounds"]


def gdn_need(cfg: dict, head_tokens: float) -> dict:
    """What the gated delta rule REQUIRES for ``head_tokens`` (token,
    value head) pairs, forward and backward, whatever the algorithm:
        operations  the recurrence's three [dk, dv] products a pair
                    (S^T k, k (beta r)^T, S^T q), 3 * 2*dk*dv, and their
                    backward twice that: 18*dk*dv
        bytes       q, k, v, o in the compute dtype (2 bytes) and g,
                    beta in float32 move once, and so do their
                    gradients; a key head's q and k serve its r value
                    heads and count once a key head:
                    2 * (2 * (2*dk/r + 2*dv) + 2 * 4)
    The chunked form's extra products (the chunk-local scores, the
    solve) and what it keeps for the way back are not required."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    r = cfg["linear_num_value_heads"] / cfg["linear_num_key_heads"]
    return {"flops": head_tokens * 18.0 * dk * dv,
            "bytes": head_tokens * 2.0 * (2 * (2 * dk / r + 2 * dv) + 8)}


def gdn_scan_roofline(ctx: Context, spec: dict):
    """The least time the chip could take for the traced rounds' gated
    delta rule (the larger of operations over the bf16 peak and bytes
    over the HBM peak) over the device time under its scope, in %."""
    ms = scope_ms_per_round(ctx, spec)
    if ms is None or ctx.peaks is None:
        return None
    traced = ctx.trace["rounds"]
    pairs = moe_readers._rows(ctx, "gdn.head_tokens", 0, traced)
    if not pairs:
        return None
    need = gdn_need(ctx.cfg, pairs)
    least_s = max(need["flops"] / ctx.peaks["bf16_flops_per_s"],
                  need["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3 * traced)
