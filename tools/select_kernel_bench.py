#!/usr/bin/env python
"""A key's exact top-1% on the local accelerator with the compaction in
its two forms: XLA's gather and scatter against the kernel of
``geomx_tpu/ops/select.py`` (PERF.md section 6, PR 63).

A JSON line a reading, milliseconds a key. ``sizes``:
``topk_by_magnitude`` whole in both forms at the key sizes the
benchmark's cells select one after the other, several keys of a flat
vector under one ``lax.map`` as the fused step runs them (a call alone
costs a millisecond of dispatch), beside the counting passes alone (the
same function with only ``t`` and ``cut`` asked for, so that XLA drops
the compaction): what either compaction costs is its whole less the
passes; the two forms compared
bit for bit on the chip, also on a key of ties, zeros, ``-0.0``, inf,
NaN and denormals. ``crossover``: the same over powers of two, which is
what ``ops.select.SELECT_MIN_ELEMS`` was read from. ``geometries``: the
kernel form over candidate (piece, group, chunk). TPU only: off the chip
the kernel is interpreted (correctness only, ``tests/test_select.py``),
so the tool exits nonzero there.

    python tools/select_kernel_bench.py sizes crossover
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools.expand_bench import _ms  # noqa: E402 — the sibling's timer

# the sizes of the keys the nine cells select one after the other (the
# size groups of over 2^22 elements), smallest first
SIZES = (589_824, 1_048_576, 1_769_472, 2_359_296, 4_194_304, 8_388_608,
         12_582_912, 16_777_216, 25_690_112, 38_597_376)
GEOMETRIES = ((256, 32, 32), (256, 16, 16), (256, 8, 8), (256, 64, 32),
              (128, 32, 32), (128, 16, 32))


def a_key(n: int, seed: int = 0, odd: bool = False) -> np.ndarray:
    """Accumulated gradients as a key holds them; ``odd``: rounded to
    halves (ties at the k-th magnitude), a third zeros of both signs,
    and an inf, a NaN and a denormal among the largest."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n, dtype=np.float32)
    if odd:
        x = np.round(x * 2) / 2
        x[rng.random(n) < 0.3] = -0.0
        x[::max(n // 7, 1)][:4] = [np.inf, -np.inf, np.nan, 1e-45]
    return x.astype(np.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", nargs="+",
                    choices=["sizes", "crossover", "geometries"])
    ap.add_argument("--out", default="chiprun_out/select_kernel_bench.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from geomx_tpu.ops import select
    from geomx_tpu.runtime import require_tpu, setup_compile_cache

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")

    def say(row):
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    say({"device": require_tpu(),
         "geometry": list(select.GEOMETRY),
         "SELECT_MIN_ELEMS": select.SELECT_MIN_ELEMS})
    setup_compile_cache()
    shipped = select.GEOMETRY

    def forms(n, k, offsets, geometry=None):
        """XLA's form, the kernel form and the passes alone, each over
        the keys at ``offsets`` of a flat vector."""
        select.GEOMETRY = geometry or shipped

        def over(form):
            return jax.jit(lambda v: jax.lax.map(
                lambda o: form(jax.lax.dynamic_slice(v, (o,), (n,))),
                offsets))

        return (over(lambda x: select.topk_by_magnitude(x, k)),
                over(lambda x: select.topk_by_magnitude(x, k, kernel=True)),
                over(lambda x: select.topk_by_magnitude(x, k)[2:]))

    def same(a, b):
        return all(bool(jnp.array_equal(
            jax.lax.bitcast_convert_type(p, jnp.int32),
            jax.lax.bitcast_convert_type(q, jnp.int32)))
                   for p, q in zip(a, b))

    def read(row, n, geometry=None):
        k = max(n // 100, 1)
        keys = row["keys"] = min(max((1 << 26) // n, 1), 16)
        offsets = jnp.arange(keys, dtype=jnp.int32) * n + 3
        xla, kernel, passes = forms(n, k, offsets, geometry)
        v = jnp.asarray(a_key(keys * n + 7))
        try:
            row["kernel_ms"] = round(_ms(kernel, v) / keys, 4)
            row["bit_equal"] = same(kernel(v), xla(v))
            if geometry is None:
                row["xla_ms"] = round(_ms(xla, v) / keys, 4)
                row["passes_ms"] = round(_ms(passes, v) / keys, 4)
                row["ratio"] = round(row["kernel_ms"] / row["xla_ms"], 3)
                odd = jnp.asarray(a_key(keys * n + 7, 1, odd=True))
                row["bit_equal_odd"] = same(kernel(odd), xla(odd))
        except Exception as e:  # noqa: BLE001 — report and move on
            row["error"] = str(e)[:300]
        finally:
            select.GEOMETRY = shipped
        say(row)
        return row

    def bad(row):
        return not (row.get("bit_equal") and row.get("bit_equal_odd", True))

    failed = False
    if "sizes" in args.what:
        for n in SIZES:
            failed |= bad(read({"what": "sizes", "elements": n}, n))
    if "crossover" in args.what:
        least = None
        for log2 in range(14, 23):
            row = read({"what": "crossover", "elements": 1 << log2},
                       1 << log2)
            failed |= bad(row)
            if least is None and row.get("ratio", 9) < 1:
                least = 1 << log2
            elif row.get("ratio", 9) >= 1:
                least = None
        say({"what": "crossover", "kernel_faster_from_elements": least})
    if "geometries" in args.what:
        for n in (SIZES[3], SIZES[-1]):
            for geometry in GEOMETRIES:
                failed |= bad(read({"what": "geometries", "elements": n,
                                    "geometry": geometry}, n, geometry))
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
