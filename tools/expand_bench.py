#!/usr/bin/env python
"""A sorted aggregate into a chunk's dense update on the local
accelerator: XLA's scatter-add against the kernel of
``geomx_tpu/ops/expand.py``, at the (slots, elements) pairs the
benchmark's cells apply (PERF.md section 6, PR 61).

A JSON line a reading. ``cells``: both forms as ``apply_chunk`` uses
them (the dense update read once by an elementwise pass), for a list
nine tenths real entries and one tenth pads, and the two compared bit
for bit by their integer images on the chip. ``fills``: the same at
cell 1's shape over the share of real entries, all in one block, and
with -0.0, inf, NaN and a denormal among the values. ``crossover``: both forms over
the number of slots at 50 elements a slot, which is what
``ops.expand.EXPAND_MIN_SLOTS`` was read from. ``geometries``: the
kernel over candidate (rows, piece, group, chunk) at cell 1's shape.
TPU only: off the chip the kernel is interpreted (correctness only,
``tests/test_expand.py``), so the tool exits nonzero there.

    python tools/expand_bench.py cells fills crossover
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# name: (elements of the chunk, slots of its upload: 2 workers x 1%).
# The seven unshaped cells apply the whole model as one chunk; cell 2
# (gpt2s-hips-bsc-wan100) applies sixteen, of three kinds
CELLS = {
    "gpt2s": (163_037_184, 3_260_556),
    "olmoe": (169_093_120, 3_381_782),
    "laguna": (248_000_000, 4_960_000),
    "qwen3next": (259_500_000, 5_190_000),
    "mellum2": (276_600_000, 5_532_000),
    "kanana2": (314_900_000, 6_298_000),
    "sdar": (248_700_000, 4_974_000),
    "wan100-blocks": (7_087_104, 141_742),
    "wan100-embedding": (38_597_376, 771_946),
    "wan100-tail": (1_536, 30),
}
GEOMETRIES = ((128, 256, 8, 8), (128, 256, 16, 16), (128, 256, 32, 32),
              (128, 256, 8, 16), (160, 256, 8, 8), (192, 256, 8, 8),
              (64, 128, 16, 16), (256, 512, 4, 8))


def a_list(size: int, slots: int, fill: float, seed: int = 0,
           clustered: bool = False, odd_values: bool = False):
    """An upload as ``DeviceResidentTrainer._chunk_up`` leaves it: about
    ``fill * slots`` real entries at distinct ascending positions under
    ``size`` (``clustered``: all of them in the first block of rows),
    then pads, slot ``s`` at ``size + s`` with value 0.0."""
    rng = np.random.default_rng(seed)
    span = min(size, 16_384) if clustered else size
    pos = np.unique(rng.integers(0, span, int(min(fill * slots, span)),
                                 dtype=np.int64))
    n = len(pos)
    positions = np.concatenate(
        [pos, size + np.arange(n, slots)]).astype(np.int32)
    values = np.zeros(slots, np.float32)
    values[:n] = rng.standard_normal(n, dtype=np.float32)
    if odd_values and n >= 8:
        values[:n:max(n // 8, 1)][:5] = [-0.0, np.inf, -np.inf, np.nan, 1e-45]
    return values, positions


def _ms(fn, *ops, iters: int = 10) -> float:
    """Median milliseconds of a call, each fenced by itself."""
    import jax

    jax.block_until_ready(fn(*ops))
    took = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*ops))
        took.append((time.perf_counter() - t0) * 1e3)
    return round(statistics.median(took), 3)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", nargs="+",
                    choices=["cells", "fills", "crossover", "geometries"])
    ap.add_argument("--only", default=",".join(CELLS),
                    help="the cells to read, by name")
    ap.add_argument("--out", default="chiprun_out/expand_bench.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from geomx_tpu.ops import expand, pallas_interpret
    from geomx_tpu.runtime import require_tpu, setup_compile_cache

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")

    def say(row):
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    say({"device": require_tpu(),
         "geometry": [expand.ROWS, expand.PIECE, expand.GROUP,
                      expand.CHUNK],
         "EXPAND_MIN_SLOTS": expand.EXPAND_MIN_SLOTS})
    setup_compile_cache()

    def applied(form):
        # as apply_chunk reads the update: once, by an elementwise pass
        return jax.jit(lambda v, p, seg: seg - 0.05 * form(v, p))

    scatters = {}

    def read(row, size, values, positions, geometry=None):
        v, p = jnp.asarray(values), jnp.asarray(positions)
        seg = jnp.ones((size,), jnp.float32)
        kernel = expand._expander(size, pallas_interpret(),
                                  *([geometry] if geometry else []))
        scatter = scatters.setdefault(size, jax.jit(
            lambda v, p: expand.scattered(v, p, size)))
        try:
            row["kernel_ms"] = _ms(applied(kernel), v, p, seg)
            if geometry is None:
                row["scatter_ms"] = _ms(applied(scatter), v, p, seg)
                row["ratio"] = round(row["kernel_ms"] / row["scatter_ms"], 3)
            same = jnp.array_equal(
                jax.lax.bitcast_convert_type(kernel(v, p), jnp.int32),
                jax.lax.bitcast_convert_type(scatter(v, p), jnp.int32))
            row["bit_equal"] = bool(same)
        except Exception as e:  # noqa: BLE001 — report and move on
            row["error"] = str(e)[:300]
        say(row)
        return row

    failed = False
    if "cells" in args.what:
        for name in args.only.split(","):
            size, slots = CELLS[name]
            row = read({"what": "cells", "cell": name, "elements": size,
                        "slots": slots}, size, *a_list(size, slots, 0.9))
            failed |= not row.get("bit_equal", False)
    if "fills" in args.what:
        size, slots = CELLS["gpt2s"]
        for tag, kw in (("fill 1.0", dict(fill=1.0)),
                        ("fill 0.5", dict(fill=0.5)),
                        ("fill 0.0", dict(fill=0.0)),
                        ("one block", dict(fill=0.9, clustered=True)),
                        ("odd values", dict(fill=0.9, odd_values=True))):
            row = read({"what": "fills", "list": tag}, size,
                       *a_list(size, slots, **kw))
            failed |= not row.get("bit_equal", False)
    if "crossover" in args.what:
        least = None
        for log2 in range(13, 23):
            slots = 1 << log2
            row = read({"what": "crossover", "slots": slots},
                       50 * slots, *a_list(50 * slots, slots, 0.9))
            failed |= not row.get("bit_equal", False)
            if least is None and row.get("ratio", 9) < 1:
                least = slots
            elif row.get("ratio", 9) >= 1:
                least = None
        say({"what": "crossover", "kernel_faster_from_slots": least})
    if "geometries" in args.what:
        size, slots = CELLS["gpt2s"]
        ops = a_list(size, slots, 0.9)
        for geometry in GEOMETRIES:
            read({"what": "geometries", "geometry": geometry}, size, *ops,
                 geometry=geometry)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
