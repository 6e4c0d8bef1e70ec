#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width of the one full-size model the repo supports:

    InProcessHiPS (2 parties x 1 worker, every role a thread, every byte
    over loopback sockets) -> kv workers -> DeviceResidentTrainer ->
    jitted fwd+BSC-select / sparse apply on the device -> party servers
    -> global server,

on the 59M decoder of examples/transformer_bsc_device.py (dim 512,
depth 8, heads 8, vocab 32768, T 512, bf16 compute, batch 8, BSC
threshold 0.01, lr 0.05, momentum 0.9). Weights are random from a fixed
seed; the data is the seeded synthetic token stream.

It passes only if: jax's default backend is a TPU; tools/chip_sanity is
ok; every Pallas kernel in the package compiled under Mosaic and matches
its reference; every loss is finite; both workers end with bit-identical
parameters that moved from the initial ones (FSA lockstep is this
repo's correctness signal); telemetry.wan_bytes() grew; and nothing
compiled after warm-up. With >= 4 devices it repeats the round on the
mesh-party tier (2 parties x 2-chip meshes) and checks placement and
the intra-party all-reduce.

The LAST stdout line of a pass is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU it exits nonzero and prints no such line: it never sets
``jax_platforms``, honours no platform override and has no CPU
fallback. Wall times it prints are information, not results.

``--rehearse`` is a labelled dry run for the CPU sandbox (tiny width,
interpreted kernels) that checks the script's own control flow before
chip time is spent; it never prints the result line and exits with
REHEARSAL_EXIT, never 0.
"""

from __future__ import annotations

import argparse
import faulthandler
import importlib.metadata
import json
import os
import re
import sys
import threading
import time
import traceback

import numpy as np

FULL = dict(dim=512, depth=8, heads=8, vocab=32768, seq=512, batch=8)
TINY = dict(dim=64, depth=2, heads=4, vocab=512, seq=32, batch=4)
THRESHOLD, LR, MOMENTUM = 0.01, 0.05, 0.9
# Every step is one complete two-tier FSA round. At full width the WAN
# tier of this configuration moves the dense fp32 aggregate both ways
# (945 MB a round, counted by telemetry), so a round is seconds of host
# wall-clock; ten keep a cold run, mesh variant included, inside the
# deadline.
STEPS = 10
FLASH_SEQS = (512, 2048)    # under and at the kernels' KERNEL_MIN_T

# One overall deadline inside the 1200 s contract: on expiry every
# thread's stack is dumped and the process exits 1. The kv timeouts sit
# well inside it so a hung barrier or round raises its OWN TimeoutError
# first (the defaults, 600 s / 300 s, would outlast the deadline).
DEADLINE_S = 1080
KV_TIMEOUTS = {"barrier_timeout_s": 120.0, "op_timeout_s": 120.0}
WORKERS_S = 900.0
REHEARSAL_EXIT = 10


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check_device(rehearse: bool) -> dict:
    import jax
    import jaxlib

    from geomx_tpu.runtime import device_stamp, require_tpu

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    stamp = device_stamp()
    say(f"platform={stamp['platform']} device_kind={stamp['kind']!r} "
        f"count={stamp['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    if not rehearse:
        require_tpu()
    return stamp


def check_sanity() -> None:
    from tools.chip_sanity import run_chip_sanity

    out = run_chip_sanity()
    for name in ("transfer_bitexact", "bitcast_in_jit", "bsc_oracle"):
        say(f"chip_sanity {name}: {out[name]}")
    say(f"chip_sanity matmul_precision: {out['matmul_precision']}")
    honest = out["blocking_honest"]
    say(f"chip_sanity blocking_honest: {honest}")
    if not honest.get("ok"):
        say("!!! block_until_ready IS NOT HONEST ON THIS BACKEND: only "
            "value-fetch-fenced timings may be trusted !!!")
    if not out["ok"]:
        raise RuntimeError(f"chip_sanity failed: {json.dumps(out)}")
    say(f"chip_sanity ok ({out['wall_s']} s)")


def _probe(attn):
    """One program: ``attn``'s output (as aux) and dq, dk, dv of its
    sum."""
    import jax
    import jax.numpy as jnp

    def f(q, k, v):
        out = attn(q, k, v).astype(jnp.float32)
        return out.sum(), out

    return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))


def check_kernels(on_chip: bool) -> None:
    """Compile and run every Pallas kernel in the package against its
    reference at the shapes the repo uses; on the chip, require the
    Mosaic call in the lowering (compiled, not interpreted)."""
    import jax
    import jax.numpy as jnp

    from geomx_tpu.models.transformer import dense_attention
    from geomx_tpu.ops.flash_attention import flash_attention

    B, H, D = 2, 8, 64
    for T in (FLASH_SEQS if on_chip else (64,)):
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, T, H, D),
                                     jnp.bfloat16) for i in range(3))
        flash, dense = _probe(flash_attention), _probe(dense_attention)
        # an interpreted kernel lowers to plain HLO: the Mosaic custom
        # call IS the proof that it compiled, and a gradient holds two
        # (the forward kernel and the ONE backward kernel)
        calls = flash.lower(q, k, v).as_text().count("tpu_custom_call")
        if on_chip and calls != 2:
            raise RuntimeError(
                f"flash attention T={T}: {calls} Mosaic calls in the "
                "gradient's lowering, not the forward kernel and the one "
                "backward kernel")
        t0 = time.perf_counter()
        ((_s, out), g), ((_sr, ref), gr) = flash(q, k, v), dense(q, k, v)
        # bf16 operands: one ulp at the output scale is ~2^-7 relative
        got = [np.asarray(x, np.float32) for x in (out, *g)]
        want = [np.asarray(x, np.float32) for x in (ref, *gr)]
        errs = [float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))
                for a, b in zip(got, want)]
        say(f"flash attention T={T} bf16 D={D}: {calls} Mosaic calls in "
            f"the gradient's lowering, rel err fwd/dq/dk/dv = "
            f"{[round(e, 4) for e in errs]} "
            f"({time.perf_counter() - t0:.1f} s incl. compile)")
        if not all(np.isfinite(e) and e < 0.03 for e in errs):
            raise RuntimeError(f"flash attention T={T} disagrees with the "
                               f"dense reference: {errs}")


def check_attention_paths(on_chip: bool) -> None:
    """The five families' full causal attention as their blocks call
    it (``transformer.causal_attention``), at their head shapes and the
    cells' lengths (Mellum2's and Kanana's 8,192, the others' 4,096;
    Kanana's latent core has a query/key head of 192 beside a value head
    of 128), Laguna's and Mellum2's sliding layers
    (``transformer.window_core``: the kernels under their window rule,
    scope ``attention_window``), and the sixth family's block-diffusion attention
    (``transformer.block_diffusion_attention``: 8,192 positions, the
    two copies of 4,096 tokens, under the block mask): on the chip the
    kernels must be in the block's own
    lowering (a Mosaic call under the scope ``attention`` /
    ``attention_full`` / ``attention_latent`` / ``attention_blockdiff``)
    and agree with the dense
    product; off it the rule must give the dense product. Beside them
    Qwen3-Next's LINEAR block at the cell's head sizes (``ops/
    gated_delta.py``): on the chip its lowering holds the chain's Mosaic
    calls under ``gated_delta_rule``, and under that scope no
    ``triangular_solve`` and no ``while`` but the solve's own 16 rows;
    off it the rule must give the scan."""
    import jax
    import jax.numpy as jnp

    from geomx_tpu.models.kanana import KananaBlock
    from geomx_tpu.models.laguna import LagunaBlock
    from geomx_tpu.models.mellum import MellumBlock
    from geomx_tpu.models.olmoe import OlmoeBlock
    from geomx_tpu.models.qwen3_next import Qwen3NextBlock
    from geomx_tpu.models.sdar import SdarBlock
    from geomx_tpu.models.transformer import (causal_attention,
                                              dense_attention,
                                              grouped_attention)

    lengths = dict.fromkeys(("olmoe", "laguna", "qwen3next",
                             "qwen3next_linear"), 4096 if on_chip else 32)
    lengths["mellum"] = lengths["kanana"] = 8192 if on_chip else 32
    lengths["laguna_window"] = lengths["laguna"]
    lengths["mellum_window"] = lengths["mellum"]
    lengths["sdar"] = 8192 if on_chip else 32   # both copies
    bf = jnp.bfloat16
    rope = dict(rope_type="default", rope_theta=10000.0,
                partial_rotary_factor=0.5)
    blocks = {
        "olmoe": OlmoeBlock(dim=256, heads=2, num_experts=4,
                            experts_per_token=2, expert_width=64,
                            local_experts=(0, 4), compute_dtype=bf),
        "laguna": LagunaBlock(
            dim=256, head_dim=128, kind="full_attention",
            query_heads=(0, 6), key_value_heads=(0, 1), window=512,
            rope=rope, sparse=False, dense_width=64, num_experts=4,
            experts_per_token=2, expert_width=64, shared_width=64,
            local_experts=(0, 4), routed_scale=1.0, compute_dtype=bf),
        "qwen3next": Qwen3NextBlock(
            dim=256, kind="full_attention", head_dim=256,
            query_heads=(0, 8), key_value_heads=(0, 1), rope=rope,
            linear_key_dim=8, linear_value_dim=8, linear_key_heads=(0, 1),
            linear_value_heads=(0, 1), conv_kernel=4, num_experts=4,
            experts_per_token=2, expert_width=64, shared_width=64,
            local_experts=(0, 4), compute_dtype=bf),
        "qwen3next_linear": Qwen3NextBlock(
            dim=256, kind="linear_attention", head_dim=256,
            query_heads=(0, 8), key_value_heads=(0, 1), rope=rope,
            linear_key_dim=128, linear_value_dim=128,
            linear_key_heads=(0, 2), linear_value_heads=(0, 4),
            conv_kernel=4, num_experts=4, experts_per_token=2,
            expert_width=64, shared_width=64, local_experts=(0, 4),
            compute_dtype=bf),
        "mellum": MellumBlock(
            dim=256, head_dim=128, kind="full_attention",
            query_heads=(0, 8), key_value_heads=(0, 1), window=1024,
            rope=dict(rope_type="yarn", rope_theta=500000, factor=16,
                      original_max_position_embeddings=8192, beta_fast=32,
                      beta_slow=1, attention_factor=1.2772588722239782),
            num_experts=4, experts_per_token=2, expert_width=64,
            local_experts=(0, 4), compute_dtype=bf),
        "kanana": KananaBlock(
            dim=256, nope_dim=128, rope_dim=64, value_dim=128,
            latent_rank=512, heads=(0, 4), rope_theta=1e6, sparse=False,
            dense_width=64, num_experts=4, experts_per_token=2,
            expert_width=64, shared_width=64, local_experts=(0, 4),
            routed_scale=1.0, compute_dtype=bf),
        "sdar": SdarBlock(
            dim=256, head_dim=128, query_heads=(0, 8),
            key_value_heads=(0, 1), block_length=4, rope_theta=1e6,
            num_experts=4, experts_per_token=2, expert_width=64,
            local_experts=(0, 4), compute_dtype=bf),
    }
    # the sliding layers of the two families that have them
    for name in ("laguna", "mellum"):
        blocks[name + "_window"] = blocks[name].clone(
            kind="sliding_attention")
    scopes = {"olmoe": "attention", "kanana": "attention_latent",
              "sdar": "attention_blockdiff",
              "qwen3next_linear": "gated_delta_rule",
              "laguna_window": "attention_window",
              "mellum_window": "attention_window"}
    for name, block in blocks.items():
        T = lengths[name]
        x = jax.random.normal(jax.random.PRNGKey(0), (1, T, 256),
                              jnp.float32)
        variables = jax.eval_shape(block.init, jax.random.PRNGKey(1), x)
        lowered = jax.jit(jax.grad(
            lambda v, x: block.apply(v, x)[0].sum())).lower(variables, x)
        calls = lowered.as_text().count("tpu_custom_call")
        if on_chip != bool(calls):
            raise RuntimeError(
                f"{name} block at T={T}: {calls} Mosaic calls in the "
                f"lowering, the rule should give "
                f"{'the kernels' if on_chip else 'the dense product'}")
        if on_chip:
            # the compiled program names an operation's scopes; XLA's
            # own grouped matmul is a Mosaic call too, under no scope
            scope = scopes.get(name, "attention_full")
            compiled = lowered.compile().as_text().splitlines()
            named = [re.search(r'op_name="([^"]*)"', line).group(1)
                     for line in compiled
                     if "tpu_custom_call" in line and "pallas_call" in line]
            if len(named) != calls or not all(
                    f"/{scope}/" in n for n in named):
                raise RuntimeError(f"{name} block: the kernels are not "
                                   f"under the scope {scope!r}: {named}")
            # the rule's dependent chains are the kernels' and the
            # solve's one loop over a block's rows: no other loop and no
            # solve of XLA's under the scope
            loops = [line.strip()[:200] for line in compiled
                     if f"/{scope}/" in line and (
                         "triangular" in line or " while(" in line
                         and "_unit_lower_inverse" not in line)]
            if scope == "gated_delta_rule" and loops:
                raise RuntimeError(f"{name} block: a while or a "
                                   f"triangular_solve under {scope!r}: "
                                   f"{loops[:3]}")
        say(f"{name} block T={T}: {calls} Mosaic calls in the lowering")
    check_block_mask_kernels(on_chip)
    check_window_kernels(on_chip)
    if not on_chip:
        return
    shapes = {"olmoe": ((16, 128), (16, 128)),
              "laguna": ((1, 6, 128), (1, 128)),
              "qwen3next": ((1, 8, 256), (1, 256)),
              "mellum": ((1, 8, 128), (1, 128)),
              "kanana": ((4, 192), (4, 192), (4, 128))}
    for name, (q_heads, *kv_heads) in shapes.items():
        T = lengths[name]
        q_shape = (1, T) + q_heads
        q = jax.random.normal(jax.random.PRNGKey(2), q_shape, bf)
        # a value head of its own size where the family names one
        k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, T) + heads, bf)
                for i, heads in ((3, kv_heads[0]), (4, kv_heads[-1])))
        dense = grouped_attention if len(q_shape) == 5 else dense_attention
        ((_s, out), got), ((_r, ref), want) = _probe(causal_attention)(
            q, k, v), _probe(lambda q, k, v: dense(
                q, k, v, scores_dtype=jnp.float32))(q, k, v)
        got, want = (out, *got), (ref, *want)
        errs = [float(jnp.linalg.norm((a - b).astype(jnp.float32))
                      / jnp.linalg.norm(b.astype(jnp.float32)))
                for a, b in zip(got, want)]
        say(f"{name} attention T={T}: rel l2 err fwd/dq/dk/dv = "
            f"{[round(e, 5) for e in errs]}")
        if not all(np.isfinite(e) and e < 0.01 for e in errs):
            raise RuntimeError(f"{name} attention on the kernels disagrees "
                               f"with the dense product: {errs}")
    check_gated_delta_forms()


def _hold_kernels_to(what: str, kernels, reference, operands,
                     on_chip: bool) -> None:
    """Output, dQ, dK and dV of ``kernels(q, k, v)`` against
    ``reference``'s, relative L2 errors under 1%; on the chip the
    kernels' lowering must hold a Mosaic call."""
    import jax.numpy as jnp

    kernel = _probe(kernels)
    if on_chip and "tpu_custom_call" not in kernel.lower(
            *operands).as_text():
        raise RuntimeError(f"{what}: no Mosaic call in the lowering")
    ((_s, out), got), ((_r, ref), want) = kernel(*operands), _probe(
        reference)(*operands)
    errs = [float(jnp.linalg.norm((a - b).astype(jnp.float32))
                  / jnp.linalg.norm(b.astype(jnp.float32)))
            for a, b in zip((out, *got), (ref, *want))]
    say(f"{what}: rel l2 err fwd/dq/dk/dv = {[round(e, 5) for e in errs]}")
    if not all(np.isfinite(e) and e < 0.01 for e in errs):
        raise RuntimeError(f"{what} disagree with their reference: {errs}")


def check_block_mask_kernels(on_chip: bool) -> None:
    """The kernels under the block mask of block-diffusion training
    against the dense product under the mask written out (forward, dQ,
    dK, dV): at tiles of 128 x 128 over 1,024 positions (112 live tiles
    of 64, every boundary of the mask inside some tile), and on the chip
    at the cell's own tiles over 8,192 positions with grouped queries."""
    import jax
    import jax.numpy as jnp

    from geomx_tpu.models.transformer import dense_block_diffusion_attention
    from geomx_tpu.ops.flash_attention import flash_attention

    cases = [(512 if on_chip else 32, 2, 128)]
    if on_chip:
        cases.append((4096, 4, None))
    for t, group, tile in cases:
        q = jax.random.normal(jax.random.PRNGKey(5), (1, 2 * t, 1, group,
                                                      128), jnp.bfloat16)
        k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2 * t, 1, 128),
                                  jnp.bfloat16) for i in (6, 7))
        _hold_kernels_to(
            f"block-mask kernels, {2 * t} positions in tiles of "
            f"{tile or 'the cell'}",
            lambda q, k, v: flash_attention(
                q, k, v, block_mask=(t, 4), block_q=tile, block_k=tile),
            lambda q, k, v: dense_block_diffusion_attention(q, k, v, 4),
            (q, k, v), on_chip)


def check_window_kernels(on_chip: bool) -> None:
    """The kernels under the sliding window against the blocked product
    ``transformer.window_attention`` with float32 scores (forward, dQ,
    dK, dV): at tiles of 128 x 128 with a window no multiple of a tile,
    and on the chip at the two cells' shapes and own tiles (window
    1,024 at 8,192 positions, 512 at 4,096; 8 queries a key/value
    head)."""
    import jax
    import jax.numpy as jnp

    from geomx_tpu.models.transformer import window_attention
    from geomx_tpu.ops.flash_attention import flash_attention

    cases = [(1024 if on_chip else 64, 200 if on_chip else 24, 2,
              128 if on_chip else 16)]
    if on_chip:
        cases += [(8192, 1024, 8, None), (4096, 512, 8, None)]
    for t, window, group, tile in cases:
        q = jax.random.normal(jax.random.PRNGKey(8), (1, t, 1, group, 128),
                              jnp.bfloat16)
        k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, t, 1, 128),
                                  jnp.bfloat16) for i in (9, 10))
        _hold_kernels_to(
            f"window kernels, window {window} over {t} positions in tiles "
            f"of {tile or 'the cell'}",
            lambda q, k, v: flash_attention(
                q, k, v, window=window, block_q=tile, block_k=tile),
            lambda q, k, v: window_attention(
                q, k, v, window, scores_dtype=jnp.float32),
            (q, k, v), on_chip)


def check_gated_delta_forms() -> None:
    """On the chip: the gated delta rule's kernel form (what the rule
    gives here) against its ``lax.scan`` form at the Qwen3-Next cell's
    shapes (one sequence of 4,096 tokens, 16 value heads of 128 x 128,
    bfloat16 operands): ``o`` and the five gradients. Both round their
    matmuls' operands to bfloat16 at the same places, so they differ by
    the order of float32 sums and where a cotangent is rounded."""
    import functools

    import jax
    import jax.numpy as jnp

    from geomx_tpu.ops import gated_delta
    from tools.gated_delta_bench import layer_inputs

    T = 4096
    args = layer_inputs(1, T, 16, 128, 128, seed=5)

    def probe():
        def f(*a):
            o = gated_delta.gated_delta_rule(*a, dtype=jnp.bfloat16)[0]
            return jnp.sum(jnp.sin(o)), o

        (_s, o), grads = jax.jit(jax.value_and_grad(
            f, argnums=range(5), has_aux=True))(*args)
        return (o, *grads)

    got = probe()
    asked = gated_delta.runs_kernel
    gated_delta.runs_kernel = functools.partial(asked, forced=False)
    try:
        want = probe()
    finally:
        gated_delta.runs_kernel = asked
    errs = [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            for a, b in zip(got, want)]
    say(f"gated delta rule T={T}: kernel form against the scan form, rel "
        f"l2 err o/dq/dk/dv/dg/dbeta = {[round(e, 5) for e in errs]}")
    if not all(np.isfinite(e) and e < 0.02 for e in errs):
        raise RuntimeError("the gated delta rule's kernel form disagrees "
                           f"with its scan form: {errs}")


def check_expand(on_chip: bool) -> None:
    """A sorted list made dense (``ops/expand.py``, the trainer's
    apply): on the chip a list of 2^20 slots takes the kernel by the
    op's own rule, as one Mosaic call, and is the scatter-add's result
    bit for bit; off the chip the rule gives the scatter and the kernel
    is driven interpreted at a small size."""
    import jax
    import jax.numpy as jnp

    from geomx_tpu.ops import expand
    from tools.expand_bench import a_list

    slots = 1 << 20 if on_chip else 1 << 11
    size = 50 * slots
    v, p = (jnp.asarray(a) for a in a_list(size, slots, 0.9,
                                           odd_values=True))
    if expand.runs_kernel(p) != on_chip:
        raise RuntimeError(f"the expand rule on this backend for {slots} "
                           f"slots: {expand.runs_kernel(p)}")
    kernel = expand._expander(size, not on_chip)
    if on_chip and kernel.lower(v, p).as_text().count(
            "tpu_custom_call") != 1:
        raise RuntimeError("the expand kernel is not one Mosaic call")
    got, want = kernel(v, p), expand.scattered(v, p, size)
    same = bool(jnp.array_equal(
        jax.lax.bitcast_convert_type(got, jnp.int32),
        jax.lax.bitcast_convert_type(want, jnp.int32)))
    say(f"expand: {slots} slots into {size} elements, kernel "
        f"{'compiled' if on_chip else 'interpreted'}, bit-equal to the "
        f"scatter-add: {same}")
    if not same:
        raise RuntimeError("the expand kernel disagrees with the scatter")


# the largest key of each of the benchmark's cells (PERF.md section 4):
# GPT-2's, OLMoE's, Laguna's, Qwen3-Next's and SDAR's, Mellum2's,
# Kanana's, Ouro's
LARGEST_KEYS = (38_597_376, 33_554_432, 25_690_112, 38_895_616, 28_311_552,
                32_833_536, 12_582_912)


def check_select(on_chip: bool) -> None:
    """A key's exact top-1% with the compaction in its kernel form
    (``ops/select.py``, the trainer's selection): on the chip a key of
    each cell's largest size takes the kernel by the op's own rule, as
    one Mosaic call, and gives XLA's form's positions, values,
    threshold and cut bit for bit, on a key of ties, zeros of both
    signs, an inf, a NaN and a denormal; off the chip the rule gives
    XLA's form and the kernel is driven interpreted at a small size."""
    import jax
    import jax.numpy as jnp

    from geomx_tpu.ops import select
    from tools.select_kernel_bench import a_key

    shipped = select.GEOMETRY
    if not on_chip:
        select.GEOMETRY = (128, 2, 8)
    try:
        for n in LARGEST_KEYS if on_chip else (40_000,):
            k = n // 100
            x = jnp.asarray(a_key(n, seed=n % 97, odd=True))
            if select.runs_kernel(x, n) != on_chip:
                raise RuntimeError(f"the selection's rule on this backend "
                                   f"for {n} elements: "
                                   f"{select.runs_kernel(x, n)}")
            kernel = jax.jit(lambda x, k=k: select.topk_by_magnitude(
                x, k, kernel=True))
            if on_chip and kernel.lower(x).as_text().count(
                    "tpu_custom_call") != 1:
                raise RuntimeError("the selection's kernel form is not "
                                   "one Mosaic call")
            got = kernel(x)
            want = jax.jit(lambda x, k=k: select.topk_by_magnitude(x, k))(x)
            same = all(bool(jnp.array_equal(
                jax.lax.bitcast_convert_type(a, jnp.int32),
                jax.lax.bitcast_convert_type(b, jnp.int32)))
                       for a, b in zip(got, want))
            say(f"select: top {k} of {n} elements, compaction "
                f"{'compiled' if on_chip else 'interpreted'}, bit-equal "
                f"to XLA's form: {same}")
            if not same:
                raise RuntimeError("the selection's kernel form disagrees "
                                   "with XLA's")
    finally:
        select.GEOMETRY = shipped


def run_round(shape: dict, steps: int, compiles, mesh_party: bool) -> None:
    """The main path: warm up outside the FSA round, take ``steps``
    steps through a live 2-party topology, stop it, check the outcome."""
    import jax.numpy as jnp

    from examples.transformer_bsc_device import (
        build_transformer_grad_step, synth_batch)
    from geomx_tpu import telemetry
    from geomx_tpu.simulate import InProcessHiPS
    from geomx_tpu.trainer_device import DeviceResidentTrainer

    tag = "mesh-party" if mesh_party else "hips-bsc"
    layout = (dict(num_parties=2, workers_per_party=2, party_mesh_size=2)
              if mesh_party else dict(num_parties=2, workers_per_party=1))
    telemetry.enable(True)
    t0 = time.perf_counter()
    topo = InProcessHiPS(**layout, extra_cfg=KV_TIMEOUTS).start()
    # built once: grad_step is a pure function, each trainer traces it
    leaves0, grad_step = build_transformer_grad_step(
        shape["dim"], shape["depth"], shape["heads"], shape["vocab"],
        shape["seq"])
    flat0 = np.concatenate([l.ravel() for l in leaves0])
    say(f"{tag}: topology up, van={'+'.join(topo.van_backends())}, "
        f"{flat0.size / 1e6:.1f}M params in {len(leaves0)} keys "
        f"({time.perf_counter() - t0:.1f} s)")

    n = len(topo.workers)
    compile_lock = threading.Lock()
    warm = threading.Barrier(n)
    res = [None] * n

    def master_init(kv):
        for i, leaf in enumerate(leaves0):
            kv.init(i, leaf)
        kv.wait()

    def worker(kv):
        try:
            _worker(kv)
        except BaseException:
            warm.abort()    # release the peer now, not at its timeout
            raise

    def _worker(kv):
        w = topo.workers.index(kv)
        t_boot = time.perf_counter()
        tr = DeviceResidentTrainer(
            list(leaves0), kv, grad_step, threshold=THRESHOLD,
            learning_rate=LR, momentum=MOMENTUM)
        t_boot = time.perf_counter() - t_boot
        rng = np.random.default_rng(1234 + w)
        batches = [synth_batch(rng, shape["batch"], shape["seq"],
                               shape["vocab"]) for _ in range(4)]
        if not mesh_party:
            batches = [jnp.asarray(b) for b in batches]
        info = {"boot_s": t_boot, "k": tr.k}
        with compile_lock:
            # trace+compile outside the FSA round (tr.step would barrier
            # on the peer and deadlock against the lock); serialized so
            # the second worker's compile hits the persistent cache
            c0, t_c = compiles.seconds, time.perf_counter()
            tr.warmup(batches[0], None)
            info["warm_s"] = time.perf_counter() - t_c
            info["compile_s"] = compiles.seconds - c0
            if mesh_party:
                info.update(_inspect_mesh(tr, w, batches[0]))
        warm.wait(WORKERS_S)
        info["programs_at_warm"] = compiles.programs
        info["wan0"] = telemetry.wan_bytes()
        losses, step_s = [], []
        for it in range(steps):
            t_s = time.perf_counter()
            losses.append(tr.step(batches[it % len(batches)], None))
            step_s.append(time.perf_counter() - t_s)
        info["losses"], info["step_s"] = losses, step_s
        info["flat"] = np.asarray(tr._flat)
        if mesh_party:
            shards = [np.asarray(s.data) for s in
                      tr._flat.addressable_shards]
            info["replicas_equal"] = all(
                np.array_equal(shards[0].view(np.uint32),
                               s.view(np.uint32)) for s in shards[1:])
        res[w] = info

    topo.run_workers(worker, include_master=master_init, timeout=WORKERS_S)
    programs_end, wan_end = compiles.programs, telemetry.wan_bytes()
    wan_codecs = sorted(telemetry.wan_bytes_by_codec())
    topo.stop()     # re-raises any node's error

    for w, r in enumerate(res):
        say(f"{tag} worker {w}: bootstrap {r['boot_s']:.1f} s, warm-up "
            f"{r['warm_s']:.1f} s (compile {r['compile_s']:.1f} s), "
            f"selects {r['k']} of {flat0.size} per round, loss "
            f"{r['losses'][0]:.4f} -> {r['losses'][-1]:.4f}, median "
            f"step {np.median(r['step_s']) * 1e3:.0f} ms [information]")
        if not np.isfinite(r["losses"]).all():
            raise RuntimeError(f"{tag} worker {w}: non-finite loss "
                               f"{r['losses']}")
        if mesh_party:
            say(f"{tag} worker {w}: state on devices {r['state_devices']}"
                f", all-reduce groups {r['allreduce_groups']}")
            if not r["replicas_equal"]:
                raise RuntimeError(f"{tag} worker {w}: mesh replicas of "
                                   "the parameters differ")
    bits = [r["flat"].view(np.uint32) for r in res]
    if not all(np.array_equal(bits[0], b) for b in bits[1:]):
        raise RuntimeError(f"{tag}: workers' parameters are not "
                           "bit-identical after the run (FSA lockstep)")
    if np.array_equal(bits[0], flat0.view(np.uint32)):
        raise RuntimeError(f"{tag}: parameters never moved from init")
    wan = wan_end - max(r["wan0"] for r in res)
    if wan <= 0:
        raise RuntimeError(f"{tag}: telemetry counted no WAN bytes")
    late = programs_end - max(r["programs_at_warm"] for r in res)
    if late:
        raise RuntimeError(f"{tag}: {late} XLA program(s) compiled after "
                           "warm-up")
    say(f"{tag}: PASS — {steps} steps, workers bit-identical and moved, "
        f"{wan / steps / 1e3:.1f} kB WAN per round (wire codecs "
        f"{wan_codecs}), 0 compiles after warm-up, "
        f"{compiles.cache_hits} persistent-cache hits so far")


def _inspect_mesh(tr, party: int, batch) -> dict:
    """Mesh-party placement: the party's state lives on its own chips
    only, and its compiled step reduces across exactly those."""
    import jax

    devs = jax.devices()
    want = {d.id for d in devs[2 * party:2 * party + 2]}
    for name in ("_flat", "_u", "_v", "_mom"):
        got = {d.id for d in getattr(tr, name).sharding.device_set}
        if got != want:
            raise RuntimeError(f"party {party}: {name} on devices {got}, "
                               f"expected {want}")
    X, y = tr._place_batch(batch, None)
    hlo = tr._fwd_chunks.lower(tr._flat, tr._u, tr._v, X, y).compile().as_text()
    # replica_groups prints as {{0,1}} or, in iota form, [groups,size]<=[n]
    groups = sorted(set(re.findall(
        r"all-reduce[^\n]*?replica_groups="
        r"(\{\{[\d,]*\}[\d,{}]*\}|\[[\d,]+\]<=\[[\d,]+\])", hlo)))
    sizes = {len(g[2:g.index("}")].split(",")) if g.startswith("{")
             else int(g[1:g.index("]")].split(",")[-1]) for g in groups}
    if sizes != {2}:
        raise RuntimeError(
            f"party {party}: expected 2-device all-reduces in the compiled "
            f"step, found replica_groups {groups or 'none'}")
    return {"state_devices": sorted(want), "allreduce_groups": groups}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dry run at tiny width; never a chip result")
    ap.add_argument("--steps", type=int, default=STEPS)
    args = ap.parse_args(argv)

    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    t_start = time.perf_counter()
    if args.rehearse:
        say("DRY RUN on whatever backend jax picked: tiny width, "
            "interpreted kernels — NOT a chip result")

    from geomx_tpu.runtime import CompileCounter, setup_compile_cache

    stamp = check_device(args.rehearse)
    say(f"compile cache: {setup_compile_cache()}")
    compiles = CompileCounter()
    check_sanity()
    check_kernels(on_chip=not args.rehearse)
    check_attention_paths(on_chip=not args.rehearse)
    check_expand(on_chip=not args.rehearse)
    check_select(on_chip=not args.rehearse)
    shape = TINY if args.rehearse else FULL
    run_round(shape, args.steps, compiles, mesh_party=False)
    if stamp["count"] >= 4:
        run_round(shape, args.steps, compiles, mesh_party=True)
    else:
        say(f"mesh-party: not run ({stamp['count']} devices)")
    say(f"total {time.perf_counter() - t_start:.0f} s, "
        f"{compiles.programs} programs built or loaded "
        f"({compiles.seconds:.0f} s), {compiles.cache_hits} from the "
        "persistent cache [information]")
    faulthandler.cancel_dump_traceback_later()
    if args.rehearse:
        say(f"DRY RUN complete — no result; exit {REHEARSAL_EXIT}")
        return REHEARSAL_EXIT
    print(json.dumps({"ok": True, "device": stamp}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 — report, then exit nonzero NOW
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        # a failed topology leaves daemon and native threads behind;
        # they must not hold the exit open
        os._exit(1)
    sys.exit(code)
