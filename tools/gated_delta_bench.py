#!/usr/bin/env python
"""The gated delta rule on the local accelerator: the ``lax.scan`` form
of ``ops/gated_delta.py`` against its kernel form, one pass of one
linear layer at the shapes the Qwen3-Next cell runs (its configuration's
and its cell's files: one sequence of 4,096 tokens, 16 value heads of
128 x 128, bfloat16 operands), ms a pass (PERF.md section 6, PR 47 and PR 48).

A JSON line a reading: ``forms`` (both forms: the forward alone, and
forward + backward as the layer calls the rule, under ``jax.checkpoint``,
so forward, forward again and backward), ``parts`` (what a form's pieces
cost alone: the solve as XLA's ``triangular_solve`` and as the rolled
blocked substitution, the chain's kernels forward and forward +
backward), ``heads`` (the kernel form over the value heads a grid step:
what ``HEADS_A_STEP`` was read from). TPU only: off the chip the kernels
are interpreted (correctness only, ``tests/test_qwen3_next.py``), so the
tool exits nonzero there.

    python tools/gated_delta_bench.py forms parts heads
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "benchmark/configs/qwen3-next-80b-ep64.json"
CELL = "benchmark/workloads/qwen3next-ep64-hips-bsc.json"


def cell_shapes():
    """(B, T, H, dk, dv, dtype) of one pass of the rule in the cell."""
    with open(os.path.join(ROOT, CONFIG)) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, CELL)) as f:
        cell = json.load(f)
    lo, hi = cfg["linear_value_heads_held"]
    return (cfg["microbatch_sequences"], cell["seq_len"], hi - lo,
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["compute_dtype"])


def layer_inputs(b: int, t: int, h: int, dk: int, dv: int, seed: int = 0):
    """(q, k, v, g, beta) as the layer makes them: q and k of unit
    length (q over sqrt(dk)), beta in (0, 1), log decays spread from
    near 0 down; float32."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(jax.random.normal(keys[0], (b, t, h, dk))) / dk ** 0.5,
            unit(jax.random.normal(keys[1], (b, t, h, dk))),
            jax.random.normal(keys[2], (b, t, h, dv)),
            -jnp.exp(jax.random.uniform(keys[3], (b, t, h), minval=-9.0,
                                        maxval=1.0)),
            jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h))))


def _time(fn, args, at: int, iters=20):
    """Milliseconds a call of ``fn(*args)``, whose result (or, of a
    tuple of cotangents, the one at ``at``) has the shape of
    ``args[at]``: iterations thread it back as that argument, so the
    dispatched chain is data-dependent end to end, and the clock stops
    on a SCALAR fetch of the last one; the fetch's round-trip is
    measured separately and subtracted."""
    import jax.numpy as jnp

    def fence(x):
        return float(jnp.sum(x.astype(jnp.float32)))

    def call(x):
        r = fn(*args[:at], x, *args[at + 1:])
        return r[at] if isinstance(r, tuple) else r

    x = call(args[at])
    fence(x)                                    # compiled, and drained
    t0 = time.perf_counter()
    fence(x)
    rtt = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        x = call(x)
    fence(x)
    return max(time.perf_counter() - t0 - rtt, 1e-9) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", nargs="+", choices=["forms", "parts", "heads"])
    ap.add_argument("--out", default="chiprun_out/gated_delta_bench.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from geomx_tpu.ops import gated_delta, pallas_interpret
    from geomx_tpu.runtime import require_tpu, setup_compile_cache

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")

    def say(row):
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    say({"device": require_tpu()})
    setup_compile_cache()
    b, t, h, dk, dv, dtype = cell_shapes()
    say({"shapes": dict(b=b, t=t, h=h, dk=dk, dv=dv, dtype=dtype,
                        chunks=gated_delta.chunks_of(t))})

    operands = layer_inputs(b, t, h, dk, dv)
    keys = jax.random.split(jax.random.PRNGKey(1), 5)

    def rule(kernel: bool):
        """The rule in one form, whatever :func:`runs_kernel` would say
        (a fresh function a form: a cached trace asks nothing)."""
        def run(q, k, v, g, beta):
            asked = gated_delta.runs_kernel
            gated_delta.runs_kernel = functools.partial(asked, forced=kernel)
            try:
                return gated_delta.gated_delta_rule(q, k, v, g, beta,
                                                    dtype=dtype)[0]
            finally:
                gated_delta.runs_kernel = asked
        return run

    def forward(kernel):
        return jax.jit(rule(kernel))

    def pass_of(kernel):
        """Forward, forward again and backward, as ``GatedDeltaNet``
        calls the rule; all five cotangents."""
        core = jax.checkpoint(rule(kernel))
        return jax.jit(jax.grad(
            lambda q, k, v, g, beta: core(q, k, v, g, beta).sum(),
            argnums=(0, 1, 2, 3, 4)))

    def read(row, make, operands=operands):
        try:
            row["ms"] = round(_time(make(), operands, 2), 4)   # v
        except Exception as e:  # noqa: BLE001 — report and move on
            row["error"] = str(e)[:300]
        say(row)

    if "forms" in args.what:
        for form, kernel in (("scan", False), ("kernel", True)):
            read({"read": "forms", "form": form, "what": "forward"},
                 lambda: forward(kernel))
            read({"read": "forms", "form": form,
                  "what": "forward+forward+backward"},
                 lambda: pass_of(kernel))
    if "heads" in args.what:
        kept = gated_delta.HEADS_A_STEP

        def retrace():
            # the kernel form sits under a jit of its own, the calls
            # under a cache: neither knows HEADS_A_STEP
            gated_delta._chain_calls.cache_clear()
            gated_delta._chunked_under_jit.clear_cache()

        for heads in (1, 2, 4, 8, 16):
            if h % heads:
                continue
            gated_delta.HEADS_A_STEP = heads
            retrace()
            read({"read": "heads", "heads_a_step": heads,
                  "what": "forward"}, lambda: forward(True))
            read({"read": "heads", "heads_a_step": heads,
                  "what": "forward+forward+backward"},
                 lambda: pass_of(True))
        gated_delta.HEADS_A_STEP = kept
        retrace()
    if "parts" in args.what:
        n, chunk = gated_delta.chunks_of(t), gated_delta.CHUNK
        a = jnp.tril(jax.random.normal(keys[0], (b, h, n, chunk, chunk))
                     * 0.1, -1)
        sides = jax.random.normal(keys[1], (b, h, n, chunk, dk + dv))

        def solve_xla(a, sides):
            return jax.lax.linalg.triangular_solve(
                a, sides, left_side=True, lower=True, unit_diagonal=True)

        def solve_rolled(a, sides):
            return gated_delta._unit_lower_solve(a, sides)[0]

        def timed(fn, x, y):
            return _time(fn, (x, y), 1)

        for name, fn in (("triangular_solve", solve_xla),
                         ("rolled_blocks", solve_rolled)):
            say({"read": "parts", "part": "solve", "form": name,
                 "what": "forward",
                 "ms": round(timed(jax.jit(fn), a, sides), 4)})
            grads = jax.jit(jax.grad(lambda a, s: fn(a, s).sum(),
                                     argnums=(0, 1)))
            say({"read": "parts", "part": "solve", "form": name,
                 "what": "forward+backward",
                 "ms": round(timed(grads, a, sides), 4)})
        u = jax.random.normal(keys[2], (b, h, n, chunk, dv))
        w, k_to_end, q_decayed = (
            jax.random.normal(key, (b, h, n, chunk, dk)).astype(dtype) * 0.1
            for key in keys[2:])
        scores = jnp.tril(jax.random.normal(
            keys[0], (b, h, n, chunk, chunk)) * 0.1).astype(dtype)
        through = jnp.full((b, h, n, 1, dv), 0.9, jnp.float32)

        def chain(rest, u):
            return gated_delta._chain(pallas_interpret(), u, *rest)[0]

        rest = (w, k_to_end, through, q_decayed, scores)
        say({"read": "parts", "part": "chain", "what": "forward",
             "ms": round(timed(jax.jit(chain), rest, u), 4)})
        grads = jax.jit(jax.grad(lambda rest, u: chain(rest, u).sum(),
                                 argnums=(0, 1)))
        say({"read": "parts", "part": "chain", "what": "forward+backward",
             "ms": round(timed(grads, rest, u), 4)})


if __name__ == "__main__":
    main()
