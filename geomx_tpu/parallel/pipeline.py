"""Pipeline parallelism over the "pp" mesh axis (GPipe schedule).

Beyond the reference (SURVEY.md §2.3 — the rubric's PP axis), built the
TPU way: every pipeline stage is the SAME jitted program running under
``shard_map``; stage identity comes from ``lax.axis_index("pp")``,
stage parameters are stacked along a leading axis sharded ``P("pp")``
(each device holds exactly its stage's slice), and activations hop
stage-to-stage with ``lax.ppermute`` inside a ``lax.scan`` — the
fill/drain bubble falls out of scanning ``M + S - 1`` ticks for M
microbatches over S stages. ``ppermute`` is differentiable, so
``jax.grad`` through the schedule yields exact pipeline-parallel
backprop (the reverse schedule is the transposed permutation, inserted
by AD — no hand-written backward pass).

Because every device traces the same program, bubble ticks compute on
garbage and are masked out at collection time; that is the standard
static-schedule trade (XLA cannot skip work data-dependently).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_spmd", "make_pipeline_fn"]


def pipeline_spmd(stage_fn: Callable, stage_params, x_mb, *,
                  axis_name: str = "pp"):
    """Run the GPipe schedule; call INSIDE shard_map over ``axis_name``.

    ``stage_fn(params_slice, x) -> y`` applies ONE stage (activations
    keep one shape across stages). ``stage_params`` leaves have a
    leading stage axis of local length 1 (the shard_map slice of the
    ``P("pp", ...)``-sharded stack). ``x_mb``: [M, mb, ...]
    microbatches (replicated across the pp group). Returns [M, mb, ...]
    — the last stage's outputs, valid on EVERY member thanks to a final
    ppermute broadcast-from-last.
    """
    S = jax.lax.psum(1, axis_name)
    sidx = jax.lax.axis_index(axis_name)
    M = x_mb.shape[0]
    local = jax.tree_util.tree_map(lambda p: p[0], stage_params)
    zero = jnp.zeros_like(x_mb[0])
    fwd_ring = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t):
        buf = carry
        # stage 0 injects microbatch t while it exists; later stages
        # consume what arrived from the previous stage
        inj = jnp.where(t < M, x_mb[jnp.clip(t, 0, M - 1)], zero)
        x = jnp.where(sidx == 0, inj, buf)
        y = stage_fn(local, x)
        nxt = jax.lax.ppermute(y, axis_name, fwd_ring)
        return nxt, y

    _, ys = jax.lax.scan(tick, zero, jnp.arange(M + S - 1))
    # the LAST stage produced microbatch m's output at tick m + S - 1;
    # select+psum broadcasts its outputs to the whole pp group so the
    # loss is computable (and identical) everywhere. Select, not
    # multiply-by-mask: bubble ticks run stage_fn on zero-filled
    # inputs, and a NaN there would survive a *0.0 mask and poison the
    # psum
    out_last = ys[S - 1:]                       # [M, mb, ...]
    kept = jnp.where(sidx == S - 1, out_last, jnp.zeros_like(out_last))
    return jax.lax.psum(kept, axis_name)


def make_pipeline_fn(mesh: Mesh, stage_fn: Callable, *,
                     in_spec: P = P(), axis_name: str = "pp"
                     ) -> Callable[[Any, Any], Any]:
    """shard_map-wrap ``pipeline_spmd`` over ``mesh``.

    Returns ``fn(stacked_params, x_mb) -> out`` where ``stacked_params``
    leaves carry a leading stage axis (length = mesh["pp"]) and are
    sharded ``P("pp", ...)`` by the wrapper; ``x_mb`` is [M, mb, ...],
    replicated over pp. The output is replicated over pp.
    """
    def fn(stacked_params, x_mb):
        body = functools.partial(pipeline_spmd, stage_fn,
                                 axis_name=axis_name)
        param_specs = jax.tree_util.tree_map(
            lambda p: P(*([axis_name] + [None] * (p.ndim - 1))),
            stacked_params)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(param_specs, in_spec),
            out_specs=in_spec, check_vma=False,
        )(stacked_params, x_mb)

    return fn
