"""Gradient accumulation: large effective batches in bounded memory.

TPU-first shape: the microbatch loop is a ``lax.scan`` INSIDE the
jitted step (one compile, static shapes, XLA overlaps the next
microbatch's compute with gradient accumulation), not a Python loop of
device calls. Composes with data-parallel ``psum`` (accumulate locally,
all-reduce once at the end — the same trick the reference's Comm tier
plays by reducing across local devices before one PS push, comm.h:104).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

__all__ = ["accumulate_gradients"]


def accumulate_gradients(grad_fn: Callable, num_microbatches: int, *,
                         axis_name: Optional[str] = None,
                         has_aux: bool = False) -> Callable:
    """Wrap ``grad_fn(params, *batch) -> (loss, grads)`` into
    ``fn(params, *batch) -> (mean_loss, mean_grads)`` where every batch
    array carries a leading batch dim divisible by ``num_microbatches``
    (any number of batch arrays — X-only losses need no dummy labels).

    Accumulation runs in f32; the returned mean gradients are cast back
    to each parameter leaf's dtype (so ``optax.apply_updates`` cannot
    silently promote low-precision params to f32).

    With ``axis_name`` the MEAN gradient is additionally ``pmean``-ed
    over that mesh axis (call inside shard_map/pjit), so the collective
    runs once per step, not once per microbatch.

    With ``has_aux`` (``jax.value_and_grad(..., has_aux=True)``),
    ``grad_fn`` returns ``((loss, aux), grads)`` and so does ``fn``:
    ``aux`` is a pytree of counts, SUMMED over the microbatches (and
    ``psum``-ed over ``axis_name``).
    """
    if num_microbatches < 1:
        raise ValueError("num_microbatches must be >= 1")

    def fn(params, *batch):
        if not batch:
            raise ValueError("need at least one batch array")
        B = batch[0].shape[0]
        if B % num_microbatches:
            raise ValueError(
                f"batch {B} not divisible by {num_microbatches} "
                "microbatches")
        mb = B // num_microbatches
        split = tuple(a.reshape(num_microbatches, mb, *a.shape[1:])
                      for a in batch)

        def body(carry, xs):
            loss_acc, grads_acc = carry
            loss, grads = grad_fn(params, *xs)
            loss, aux = loss if has_aux else (loss, None)
            grads_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(a.dtype), grads_acc, grads)
            return (loss_acc + loss, grads_acc), aux

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, grads_sum), aux = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), split)
        aux = jax.tree_util.tree_map(lambda a: a.sum(0), aux)
        n = jnp.float32(num_microbatches)
        loss = loss_sum / n
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / n).astype(p.dtype), grads_sum, params)
        if axis_name is not None:
            loss = jax.lax.pmean(loss, axis_name)
            grads = jax.lax.pmean(grads, axis_name)
            if has_aux:
                aux = jax.lax.psum(aux, axis_name)
        return ((loss, aux), grads) if has_aux else (loss, grads)

    return fn
