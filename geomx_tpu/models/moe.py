"""Mixture-of-Experts FFN: a no-drop sparse dispatch, and a top-1 block
with expert parallelism over the "ep" axis.

Beyond the reference (its op set predates MoE; SURVEY.md §2.3 — the
rubric's EP axis). Expert weights are STACKED along a leading expert
dimension and sharded ``P("ep", ...)``.

:func:`sparse_dispatch` is the one dispatch: the (row, slot) pairs a
top-k router chose are sorted by expert, the expert FFN runs as grouped
matmuls over the group sizes (``jax.lax.ragged_dot``), the rows are
unsorted and combined with the router's weights. Work follows the rows
routed, shapes stay static, no capacity factor drops a token. It takes
``k`` from its inputs and the contiguous range of experts held here as
an argument: one rank of an expert-parallel layout computes its own
experts' terms and nothing for the others. The gates are whatever
weights the caller made of its router's scores: softmax probabilities
as they are (``models/olmoe.py``, top-8 of 64), sigmoid scores
normalised over the chosen and scaled (``models/laguna.py``, top-8 of
256 beside a shared expert that never comes here), one probability
(:class:`MoEBlock`, the ``k = 1`` case); the dispatch multiplies and
sums, it normalises nothing.

:class:`MoEBlock` router: top-1 (Switch-style) with optional jitter
noise and the standard load-balancing auxiliary loss (mean fraction x
mean gate per expert, scaled by E).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["MoEBlock", "moe_param_sharding", "is_expert_param",
           "sparse_dispatch", "gated_experts"]

# leaf names of expert-stacked params (leading axis = expert dim)
EXPERT_PARAM_NAMES = ("w_up", "b_up", "w_dn", "b_dn")


def is_expert_param(path: str) -> bool:
    """True when a '/'-joined param path names an expert-stacked leaf
    (the single source of truth for ep-sharding rules)."""
    return path.rsplit("/", 1)[-1] in EXPERT_PARAM_NAMES


def expert_spec(ndim: int) -> P:
    """PartitionSpec for an expert-stacked leaf: experts over "ep",
    everything else replicated."""
    return P(*(["ep"] + [None] * (ndim - 1)))


@jax.custom_vjp
def _take_rows(x, fwd_idx, bwd_idx):
    """``x[fwd_idx]`` for a permutation and its inverse: the cotangent
    is a gather too (``g[bwd_idx]``), never a scatter-add."""
    return x[fwd_idx]


def _take_rows_fwd(x, fwd_idx, bwd_idx):
    return x[fwd_idx], bwd_idx


def _take_rows_bwd(bwd_idx, g):
    return g[bwd_idx], None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def sparse_dispatch(h, expert_idx, gates, expert_fn, local_experts):
    """No-drop top-k dispatch to a contiguous range of experts.

    ``h`` [N, D] rows, ``expert_idx`` [N, k] the experts each row chose
    (ids of the whole router), ``gates`` [N, k] their weights, as the
    caller normalised them,
    ``local_experts`` (lo, hi) the experts held here. The (row, slot)
    pairs are sorted by expert, held experts first and in order, so
    that ``expert_fn(rows [N*k, D], group_sizes [hi-lo], row_expert
    [N*k]) -> [N*k, D_out]`` sees each expert's rows contiguous (what
    ``jax.lax.ragged_dot`` wants); the pairs of experts held elsewhere
    sort last, past the sum of the group sizes, are zero on the way in
    and on the way out and cost shape, not arithmetic. Shapes are
    static, nothing is dropped whatever the routing. Returns the rows'
    ``sum_slot gate * expert(row)`` over the held experts, [N, D_out],
    and the group sizes.
    """
    lo, hi = local_experts
    held_n = hi - lo
    n, k = expert_idx.shape
    with jax.named_scope("dispatch"):
        e = expert_idx.reshape(-1) - lo
        slot = jnp.where((e >= 0) & (e < held_n), e, held_n)
        order = jnp.argsort(slot, stable=True)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * k, dtype=order.dtype))
        row_expert = slot[order]
        group_sizes = jnp.bincount(slot, length=held_n + 1)[
            :held_n].astype(jnp.int32)
        held = (row_expert < held_n)[:, None]
        rows = _take_rows(jnp.repeat(h, k, axis=0), order, inverse)
        # a select, not a product: what a grouped matmul leaves past its
        # groups need not be a number, in either direction
        rows = jnp.where(held, rows, jnp.zeros((), rows.dtype))
    out = expert_fn(rows, group_sizes, row_expert)
    with jax.named_scope("combine"):
        out = jnp.where(held, out, jnp.zeros((), out.dtype))
        out = _take_rows(out, inverse, order).reshape(n, k, -1)
        y = jnp.einsum("nk,nkd->nd", gates.astype(out.dtype), out)
    return y, group_sizes


def gated_experts(w_gate, w_up, w_down):
    """The ``expert_fn`` of :func:`sparse_dispatch` for stacks of
    SiLU-gated experts, ``w_gate`` and ``w_up`` [E, D, W], ``w_down``
    [E, W, D]: ``(silu(x Wg_e) * (x Wu_e)) Wd_e`` as three grouped
    matmuls over the rows' groups."""
    def experts(rows, group_sizes, _row_expert):
        with jax.named_scope("expert_matmuls"):
            a = nn.silu(jax.lax.ragged_dot(rows, w_gate, group_sizes)) \
                * jax.lax.ragged_dot(rows, w_up, group_sizes)
            return jax.lax.ragged_dot(a, w_down, group_sizes)

    return experts


class MoEBlock(nn.Module):
    """Drop-in FFN block: LayerNorm -> top-1 MoE MLP -> residual."""

    dim: int
    num_experts: int = 4
    mlp_ratio: int = 4
    jitter: float = 0.0
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        dt = self.compute_dtype
        E, D, H = self.num_experts, self.dim, self.mlp_ratio * self.dim
        h = nn.LayerNorm(dtype=dt, name="ln")(x)

        # router (f32 for a stable softmax/argmax)
        logits = nn.Dense(E, dtype=jnp.float32, name="router")(
            h.astype(jnp.float32))
        if train and self.jitter > 0.0:
            rng = self.make_rng("router")
            logits = logits * jax.random.uniform(
                rng, logits.shape, minval=1.0 - self.jitter,
                maxval=1.0 + self.jitter)
        gates = jax.nn.softmax(logits, axis=-1)           # [B, T, E]
        expert_idx = jnp.argmax(gates, axis=-1)           # [B, T]
        onehot = jax.nn.one_hot(expert_idx, E, dtype=gates.dtype)
        gate_val = jnp.sum(gates * onehot, axis=-1)       # [B, T]

        # load-balancing aux loss (Switch Transformer eq. 4-6)
        frac_tokens = jnp.mean(onehot, axis=(0, 1))       # [E]
        frac_gates = jnp.mean(gates, axis=(0, 1))         # [E]
        self.sow("losses", "moe_aux",
                 E * jnp.sum(frac_tokens * frac_gates))

        # expert-stacked MLP params: [E, D, H] / [E, H, D] — shard the
        # leading axis over "ep" (moe_param_sharding); GSPMD partitions
        # the grouped matmuls from the shardings
        w_up = self.param("w_up", nn.initializers.lecun_normal(),
                          (E, D, H), jnp.float32).astype(dt)
        b_up = self.param("b_up", nn.initializers.zeros,
                          (E, H), jnp.float32).astype(dt)
        w_dn = self.param("w_dn", nn.initializers.lecun_normal(),
                          (E, H, D), jnp.float32).astype(dt)
        b_dn = self.param("b_dn", nn.initializers.zeros,
                          (E, D), jnp.float32).astype(dt)

        # top-1 is the k=1 case of the no-drop sparse dispatch: rows
        # sorted by expert, grouped matmuls over the group sizes, so
        # the work follows the rows routed and not E times the tokens
        def experts(rows, group_sizes, row_expert):
            he = jax.lax.ragged_dot(rows, w_up, group_sizes) \
                + b_up[row_expert]
            return jax.lax.ragged_dot(nn.gelu(he), w_dn, group_sizes) \
                + b_dn[row_expert]

        B, T, _ = h.shape
        y, _sizes = sparse_dispatch(
            h.reshape(B * T, D), expert_idx.reshape(B * T, 1),
            gate_val.reshape(B * T, 1), experts, (0, E))
        y = y.reshape(B, T, D)
        return x + y.astype(x.dtype)


def moe_param_sharding(mesh: Mesh):
    """device_put MoE params with experts over "ep" (router/norm
    replicated)."""

    def shard(params):
        def put(path_entries, leaf):
            path = "/".join(str(getattr(p, "key", p)) for p in path_entries)
            spec = expert_spec(leaf.ndim) if is_expert_param(path) else P()
            return jax.device_put(leaf, NamedSharding(mesh, spec))

        return jax.tree_util.tree_map_with_path(put, params)

    return shard
