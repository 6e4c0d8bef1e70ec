#!/usr/bin/env python
"""Flash vs dense attention sweep on the local accelerator.

Prints a JSON line per (T, D, causal) config with forward and
forward+backward wall times for the XLA dense einsum and the Pallas
FlashAttention-2 kernels (geomx_tpu.ops.flash_attention). TPU only: off
the chip the flash path is interpret-mode (correctness only, covered by
tests/test_flash_attention.py), so the tool exits nonzero there.

    python tools/attention_bench.py --seqs 512,1024,2048,4096
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _time(fn, q, k, v, iters=20):
    """Value-fenced timing, correct whether or not block_until_ready
    waits (tools/chip_sanity.py blocking probe). Iterations thread the
    output back into q so the dispatched chain is data-dependent end to
    end, and the clock stops on a SCALAR fetch of the last output; the
    fetch round-trip is measured separately and subtracted."""
    import jax.numpy as jnp

    def _head(out):
        return out[0] if isinstance(out, tuple) else out

    def _fence(x):
        return float(jnp.sum(x.astype(jnp.float32)))

    x = _head(fn(q, k, v))
    _fence(x)                                   # warm compile + fence
    t0 = time.perf_counter()
    _fence(x)                                   # already computed:
    rtt = time.perf_counter() - t0              # pure fetch round-trip
    t0 = time.perf_counter()
    for _ in range(iters):
        x = _head(fn(x, k, v))
    _fence(x)
    return max(time.perf_counter() - t0 - rtt, 1e-9) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=str, default="512,1024,2048,4096")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--sweep-blocks", action="store_true",
                    help="sweep flash block_q x block_k per seq len and "
                         "report the fastest fwd+bwd combo vs dense")
    ap.add_argument("--blocks", type=str, default="128,256,512",
                    help="candidate block sizes for --sweep-blocks")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from geomx_tpu.models.transformer import dense_attention
    from geomx_tpu.ops.flash_attention import flash_attention
    from geomx_tpu.runtime import require_tpu, setup_compile_cache

    print(json.dumps({"device": require_tpu()}), flush=True)
    setup_compile_cache()

    B, H, D = args.batch, args.heads, args.head_dim
    for T in [int(s) for s in args.seqs.split(",")]:
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, T, H, D),
                                     jnp.bfloat16) for i in range(3))

        dense_f = jax.jit(lambda q, k, v: dense_attention(q, k, v))
        flash_f = jax.jit(lambda q, k, v: flash_attention(q, k, v))
        dense_g = jax.jit(jax.grad(
            lambda q, k, v: dense_attention(q, k, v).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)))
        flash_g = jax.jit(jax.grad(
            lambda q, k, v: flash_attention(q, k, v).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)))

        row = {"T": T, "B": B, "H": H, "D": D, "causal": True,
               "dense_fwd_ms": round(_time(dense_f, q, k, v), 3),
               "flash_fwd_ms": round(_time(flash_f, q, k, v), 3),
               "dense_fwdbwd_ms": round(_time(dense_g, q, k, v), 3),
               "flash_fwdbwd_ms": round(_time(flash_g, q, k, v), 3)}
        row["fwd_speedup"] = round(
            row["dense_fwd_ms"] / row["flash_fwd_ms"], 2)
        row["fwdbwd_speedup"] = round(
            row["dense_fwdbwd_ms"] / row["flash_fwdbwd_ms"], 2)
        print(json.dumps(row), flush=True)

        if not args.sweep_blocks:
            continue
        # block-size sweep: the fwd+bwd time is what a train step pays
        cands = [int(b) for b in args.blocks.split(",")]
        best = None
        for bq in cands:
            for bk in cands:
                if bq > T or bk > T:
                    continue
                fg = jax.jit(jax.grad(
                    lambda q, k, v, _bq=bq, _bk=bk: flash_attention(
                        q, k, v, block_q=_bq, block_k=_bk).astype(
                        jnp.float32).sum(), argnums=(0, 1, 2)))
                try:
                    ms = _time(fg, q, k, v, iters=10)
                except Exception as e:  # noqa: BLE001 — report and move on
                    print(json.dumps({"T": T, "block_q": bq,
                                      "block_k": bk,
                                      "error": str(e)[:200]}), flush=True)
                    continue
                print(json.dumps({"T": T, "block_q": bq, "block_k": bk,
                                  "flash_fwdbwd_ms": round(ms, 3)}),
                      flush=True)
                if best is None or ms < best[0]:
                    best = (ms, bq, bk)
        if best:
            print(json.dumps({
                "T": T, "best_block_q": best[1], "best_block_k": best[2],
                "best_flash_fwdbwd_ms": round(best[0], 3),
                "dense_fwdbwd_ms": row["dense_fwdbwd_ms"],
                "best_speedup": round(
                    row["dense_fwdbwd_ms"] / best[0], 2)}), flush=True)


if __name__ == "__main__":
    main()
