"""Record a SMALL trace of real two-party rounds on the chip for the gap
readers' test (``python -m benchmark.tests.record_rounds``): the
harness's own ``--rehearse --trace 1`` run of ``gpt2s-hips-bsc`` (GPT-2
at the rehearsal's widths, two parties, three traced rounds), on the
TPU. The run's ``.xplane.pb`` holds hundreds of thousands of events
nobody here reads, so what is kept under ``chiprun_out/gap_probe/`` is
a cut of it, still an XSpace that ``jax.profiler.ProfileData`` reads:

- the first chip's plane with its ``XLA Ops`` line alone, every event
  of it with its start and duration, no stats, an operation's name cut
  to its instruction (``trace_reduce.op_name``);
- of the host planes, the threads that hold a ``bench.step`` span or a
  span of the program's table, those events alone, arguments kept.

Prints what the gap readers make of the whole trace and of the cut
(they must agree), to be pinned in ``test_gap_readers.py``."""

from __future__ import annotations

import json
import os
import sys
from typing import Callable, Dict, Iterable, List

from benchmark import gap_readers, trace_reduce
from benchmark.qwen3next_readers import _fields, _first, _message

CELL, SEED = "gpt2s-hips-bsc", 7
OUT = os.path.join("chiprun_out", "gap_probe")


# -- the protobuf wire format, written --------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        byte = n & 0x7F
        n >>= 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _put(number: int, value) -> bytes:
    """One field: a varint for an int, length-delimited for bytes."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _copy(buf: bytes, drop: Iterable[int] = ()) -> bytes:
    return b"".join(_put(n, v) for n, v in _fields(buf) if n not in drop)


def cut_plane(plane: bytes, keep_line: Callable[[str], bool],
              keep_event: Callable[[str], bool], bare: bool) -> bytes:
    """``plane`` with the lines ``keep_line`` names and, of them, the
    events ``keep_event`` names; ``bare``: no stats anywhere and names
    cut to the instruction. Empty where no event is left."""
    msg = _message(plane)
    name_of: Dict[int, str] = {}
    for entry in msg.get(4, []):
        meta = _message(_first(_message(entry), 2, b""))
        name_of[_first(meta, 1)] = _first(meta, 2, b"").decode()
    used, lines = set(), []
    for line in msg.get(3, []):
        if not keep_line(_first(_message(line), 2, b"").decode()):
            continue
        out, kept = [], 0
        for number, value in _fields(line):
            if number != 4:
                out.append(_put(number, value))
                continue
            meta = _first(_message(value), 1)
            if keep_event(name_of.get(meta, "")):
                used.add(meta)
                kept += 1
                out.append(_put(4, _copy(value, drop=(4,)) if bare
                                else value))
        if kept:
            lines.append(_put(3, b"".join(out)))
    if not lines:
        return b""
    parts = [_put(n, v) for n, v in _fields(plane) if n in (1, 2)]
    for entry in msg.get(4, []):
        meta = _first(_message(entry), 2, b"")
        ident = _first(_message(meta), 1)
        if ident not in used:
            continue
        if bare:
            name = trace_reduce.op_name(name_of[ident]).encode()
            entry = _put(1, ident) + _put(2, _put(1, ident) + _put(2, name))
        parts.append(_put(4, entry))
    if not bare:
        parts += [_put(5, e) for e in msg.get(5, [])]
    return b"".join(parts + lines)


def cut(xspace: bytes, span_names) -> bytes:
    planes, device = [], []
    for plane in _message(xspace).get(1, []):
        name = _first(_message(plane), 2, b"").decode()
        m = trace_reduce.DEVICE_RE.match(name)
        if m:
            device.append((int(m.group(1)), plane))
        else:
            planes.append(cut_plane(
                plane, lambda _line: True,
                lambda ev: (ev in span_names
                            or ev.startswith(trace_reduce.SPAN_PREFIX)),
                bare=False))
    for _chip, plane in sorted(device)[:1]:
        planes.append(cut_plane(
            plane, lambda line: line == trace_reduce.OPS_LINE,
            lambda _ev: True, bare=True))
    return b"".join(_put(1, p) for p in planes if p)


def numbers(pd) -> dict:
    got = gap_readers.split(pd)
    return {"gaps_ns": [e - s for s, e in got["gaps"]],
            "by_bucket_ns": {b: round(ns, 3)
                             for b, ns in sorted(got["by_bucket"].items())}}


def main() -> int:
    import jax

    from benchmark import run

    code = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                     "2", "--trace", "1", "--rehearse"])
    if code != run.REHEARSAL_EXIT:
        return code or 1
    if jax.default_backend() != "tpu":
        print("no chip: nothing recorded")
        return 1
    path = trace_reduce.find_xplane(os.path.join(
        "benchmark_out", "trace", f"{CELL}-{SEED}"))
    with open(path, "rb") as f:
        whole = f.read()
    small = cut(whole, set(gap_readers.span_table()))
    os.makedirs(OUT, exist_ok=True)
    out = os.path.join(OUT, "gpt2_rehearsal_rounds.xplane.pb")
    with open(out, "wb") as f:
        f.write(small)
    print(f"{path}: {len(whole)} bytes; {out}: {len(small)} bytes")
    data = jax.profiler.ProfileData
    pinned = {"whole": numbers(data.from_serialized_xspace(whole)),
              "cut": numbers(data.from_serialized_xspace(small))}
    print(json.dumps(pinned))
    return 0 if pinned["whole"] == pinned["cut"] else 1


if __name__ == "__main__":
    sys.exit(main())
