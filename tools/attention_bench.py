#!/usr/bin/env python
"""Full causal and sliding-window attention on the local accelerator:
the dense path of ``models/transformer.py`` (the blocked product of
``window_attention`` for a window) against the Pallas kernels of
``ops/flash_attention.py``, forward + backward of one pass, at the
shapes the benchmark's cells run (PERF.md section 6, PRs 39 and 50).

A JSON line a reading: ``shapes`` (the callers as they call: who
keeps the scores and who recomputes the core on the way back),
``blocks`` (the kernel over candidate ``block_q`` x ``block_k``: what
``ops.flash_attention.attention_blocks`` was read from), ``lengths``
(both forms over T: what ``models.transformer.KERNEL_MIN_T`` was read
from), ``windows`` (both forms of the window shapes over the window:
whether the rule needs a floor on it), ``splash`` (JAX's own splash
attention at the same shapes, its two backward kernels and its fused
one: ``use_fused_bwd_kernel``). TPU
only: off the chip the kernels are interpreted (correctness only,
``tests/test_flash_attention.py``), so the tool exits nonzero there.

    python tools/attention_bench.py shapes blocks lengths
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from typing import NamedTuple, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))



class Shape(NamedTuple):
    """A caller as it calls: q's and k's shape, float32 scores in the
    dense form, the core recomputed on the way back, the window, the
    block mask's (t, b), a value head's own size."""
    q: tuple
    kv: tuple
    f32: bool
    recomputed: bool
    window: Optional[int] = None
    block_mask: Optional[tuple] = None
    dv: Optional[int] = None


# GPT-2's batch is the 8 sequences its cells pass at once
SHAPES = {
    "olmoe": Shape((1, 4096, 16, 128), (1, 4096, 16, 128), True, False),
    "laguna": Shape((1, 4096, 1, 6, 128), (1, 4096, 1, 128), True, True),
    "qwen3next": Shape((1, 4096, 1, 8, 256), (1, 4096, 1, 256), True, True),
    "gpt2": Shape((8, 1023, 12, 64), (8, 1023, 12, 64), False, False),
    "mellum_window": Shape((1, 8192, 1, 8, 128), (1, 8192, 1, 128), True,
                           True, window=1024),
    "laguna_window": Shape((1, 4096, 1, 8, 128), (1, 4096, 1, 128), True,
                           True, window=512),
    "ouro": Shape((2, 4096, 16, 128), (2, 4096, 16, 128), True, True),
    "kanana": Shape((1, 8192, 4, 192), (1, 8192, 4, 192), True, True,
                    dv=128),
    "sdar": Shape((1, 8192, 1, 8, 128), (1, 8192, 1, 128), True, False,
                  block_mask=(4096, 4)),
}
BLOCKS = ((128, 128), (256, 256), (256, 512), (512, 256), (512, 512),
          (512, 1024), (1024, 512), (1024, 1024), (256, 1024), (1024, 256))


def _time(fn, q, k, v, iters=20):
    """Milliseconds a call of ``fn(q, k, v) -> (dq, dk, dv)``. Iterations
    thread dq back into q so the dispatched chain is data-dependent end
    to end, and the clock stops on a SCALAR fetch of the last output
    (a fence whether or not ``block_until_ready`` waits); the fetch
    round-trip is measured separately and subtracted."""
    import jax.numpy as jnp

    def fence(x):
        return float(jnp.sum(x.astype(jnp.float32)))

    x = fn(q, k, v)[0]
    fence(x)                                    # compiled, and drained
    t0 = time.perf_counter()
    fence(x)
    rtt = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        x = fn(x, k, v)[0]
    fence(x)
    return max(time.perf_counter() - t0 - rtt, 1e-9) / iters * 1e3


def _grad(attend):
    import jax
    import jax.numpy as jnp

    return jax.jit(jax.grad(
        lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))


def _dense(shape: Shape, window=None):
    import jax
    import jax.numpy as jnp

    from geomx_tpu.models.transformer import (
        dense_attention, dense_block_diffusion_attention, grouped_attention,
        window_attention)

    scores = jnp.float32 if shape.f32 else None
    if shape.block_mask is not None:
        core = functools.partial(dense_block_diffusion_attention,
                                 block=shape.block_mask[1])
    elif window is not None:
        core = functools.partial(window_attention, window=window,
                                 scores_dtype=scores)
    else:
        core = functools.partial(
            grouped_attention if len(shape.q) == 5 else dense_attention,
            scores_dtype=scores)
    return jax.checkpoint(core) if shape.recomputed else core


def _kernel(block_q=None, block_k=None, window=None, block_mask=None):
    from geomx_tpu.ops.flash_attention import flash_attention

    return functools.partial(flash_attention, block_q=block_q,
                             block_k=block_k, window=window,
                             block_mask=block_mask)


def _splash(shape: Shape, block: int, fused: bool):
    """JAX's splash attention behind the same contract: causal mask (or
    the block mask written out), its MQA form for one key/value head; q
    is scaled by the caller as its kernels apply none. ``fused``: its
    one backward kernel (``use_fused_bwd_kernel``: dQ leaves once a
    k-block and XLA sums the copies) in place of its two."""
    import jax
    import numpy as np
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    from geomx_tpu.models.transformer import block_diffusion_mask

    q_shape = shape.q
    t, d = q_shape[1], q_shape[-1]
    heads = q_shape[2] * (q_shape[3] if len(q_shape) == 5 else 1)
    one = sm.CausalMask((t, t)) if shape.block_mask is None else \
        sm.NumpyMask(np.asarray(block_diffusion_mask(
            *shape.block_mask, np)))
    mask = sm.MultiHeadMask([one for _ in range(heads)])
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        **(dict(use_fused_bwd_kernel=True) if fused
           else dict(block_q_dq=block, block_kv_dq=block)))
    grouped = len(q_shape) == 5
    make = sk.make_splash_mqa_single_device if grouped else sk.make_splash_mha
    kernel = make(mask, block_sizes=sizes) if grouped else make(
        mask, block_sizes=sizes, head_shards=1, q_seq_shards=1)

    def attend(q, k, v):
        q = q * (d ** -0.5)
        if grouped:                     # [B, T, 1, G, D] on [B, T, 1, D]
            o = jax.vmap(kernel)(q[:, :, 0].swapaxes(1, 2), k[:, :, 0],
                                 v[:, :, 0])
            return o.swapaxes(1, 2)[:, :, None]
        o = jax.vmap(kernel)(*(x.swapaxes(1, 2) for x in (q, k, v)))
        return o.swapaxes(1, 2)

    return attend


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", nargs="+",
                    choices=["shapes", "blocks", "lengths", "windows",
                             "splash"])
    ap.add_argument("--only", default=",".join(SHAPES),
                    help="the shapes to read, by name")
    ap.add_argument("--out", default="chiprun_out/attention_bench.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from geomx_tpu.ops.flash_attention import attention_blocks
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

    def operands(shape):
        v_shape = shape.kv[:-1] + (shape.dv or shape.kv[-1],)
        return tuple(
            jax.random.normal(jax.random.PRNGKey(i), s, jnp.bfloat16)
            for i, s in enumerate((shape.q, shape.kv, v_shape)))

    def read(row, make, ops):
        try:
            row["fwdbwd_ms"] = round(_time(_grad(make()), *ops), 4)
        except Exception as e:  # noqa: BLE001 — report and move on
            row["error"] = str(e)[:300]
        say(row)

    names = [n for n in args.only.split(",") if n]
    for name in names:
        shape = SHAPES[name]
        window, block_mask = shape.window, shape.block_mask
        ops = operands(shape)
        t, d = shape.q[1], shape.q[-1]
        blocks = attention_blocks(t, d, window)
        if "shapes" in args.what:
            read({"read": "shapes", "shape": name, "form": "dense",
                  "recomputed": shape.recomputed},
                 lambda: _dense(shape, window), ops)
            read({"read": "shapes", "shape": name, "form": "kernel",
                  "blocks": blocks},
                 lambda: _kernel(window=window, block_mask=block_mask), ops)
            if shape.recomputed:
                read({"read": "shapes", "shape": name, "form": "kernel",
                      "recomputed": True, "blocks": blocks},
                     lambda: jax.checkpoint(_kernel(
                         window=window, block_mask=block_mask)), ops)
        if "blocks" in args.what:
            for bq, bk in BLOCKS:
                if bq <= t + 7 and bk <= t + 7:
                    read({"read": "blocks", "shape": name,
                          "blocks": [bq, bk]},
                         lambda: _kernel(bq, bk, window, block_mask), ops)
        if "splash" in args.what and window is None:
            for block in (512, 1024):
                for fused in (False, True):
                    read({"read": "splash", "shape": name, "block": block,
                          "fused_bwd": fused},
                         lambda: _splash(shape, block, fused), ops)
    if "windows" in args.what:
        for name in names:
            shape = SHAPES[name]
            if shape.window is None:
                continue
            ops = operands(shape)
            for w in (128, 256, 512, 1024, 2048):
                read({"read": "windows", "shape": name, "window": w,
                      "form": "dense"}, lambda: _dense(shape, w), ops)
                read({"read": "windows", "shape": name, "window": w,
                      "form": "kernel", "blocks": attention_blocks(
                          shape.q[1], shape.q[-1], w)},
                     lambda: _kernel(window=w), ops)
    if "lengths" in args.what:
        for name in names:
            full = SHAPES[name]
            if full.block_mask is not None:     # its length is its mask's
                continue
            for t in (512, 1024, 2048, 4096):
                if t > full.q[1] + 1:
                    continue
                shape = full._replace(q=(full.q[0], t) + full.q[2:],
                                      kv=(full.kv[0], t) + full.kv[2:])
                ops = operands(shape)
                read({"read": "lengths", "shape": name, "t": t,
                      "form": "dense"},
                     lambda: _dense(shape, shape.window), ops)
                read({"read": "lengths", "shape": name, "t": t,
                      "form": "kernel",
                      "blocks": attention_blocks(t, shape.q[-1],
                                                 shape.window)},
                     lambda: _kernel(window=shape.window), ops)


if __name__ == "__main__":
    main()
