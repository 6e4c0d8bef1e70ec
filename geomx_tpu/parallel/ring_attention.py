"""Ring attention: sequence/context parallelism over the "sp" mesh axis.

Long-context capability the reference lacks entirely (SURVEY.md §5.7):
sequence length is sharded across devices; keys/values rotate around the
ring via ``ppermute`` while each device accumulates its queries' attention
with a numerically-stable streaming softmax (the blockwise/flash
recurrence), so memory per device is O(T/sp) and the ring rides the ICI.

Layout convention: [batch, seq, heads, head_dim] per shard; heads may be
sharded over "tp" (Megatron-style) — the ring only touches "sp".
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, mask):
    """One blockwise attention contribution: returns (scores_max, exp_scores
    @ v, exp_scores row-sum) for streaming-softmax accumulation.

    The returned max is stop_gradient'ed: the streaming-softmax max is pure
    numerical-stability bookkeeping (it cancels in o/l), so EVERY use of it
    — here and in the merge rescales — must be non-differentiable, else
    spurious gradient flows through each block's argmax.
    """
    d = q.shape[-1]
    # q: [B,Tq,H,D] k: [B,Tk,H,D] -> s: [B,H,Tq,Tk]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d).astype(q.dtype)
    s = jnp.where(mask, s, NEG_INF)
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))  # [B,H,Tq,1]
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)                    # [B,Tq,H,D]
    l = jnp.sum(p, axis=-1, keepdims=True)                     # [B,H,Tq,1]
    return m, o, l


def ring_attention(q, k, v, *, causal: bool = False,
                   axis_name: str = "sp"):
    """Collective ring attention; call inside shard_map over ``axis_name``.

    Each of the ``n`` ring steps computes this device's queries against the
    currently-held K/V block, then rotates K/V one hop around the ring.
    """
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    t_local = q.shape[1]

    q_pos = my_idx * t_local + jnp.arange(t_local)             # global q rows

    def mask_for(src_idx):
        k_pos = src_idx * t_local + jnp.arange(t_local)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]            # [Tq,Tk]
        else:
            mask = jnp.ones((t_local, t_local), dtype=bool)
        return mask[None, None]                                # [1,1,Tq,Tk]

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, i):
        k_blk, v_blk, m_acc, o_acc, l_acc = carry
        # rotate K/V one hop FIRST: the scan covers steps 1..n-1, step 0's
        # own block was consumed before the scan, so exactly n-1 rotations
        # happen and no final hop is wasted
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        src_idx = (my_idx - i) % n        # whose block we now hold
        m_blk, o_blk, l_blk = _block_attn(q, k_blk, v_blk, mask_for(src_idx))
        # streaming softmax merge (all maxes are stop_gradient'ed)
        m_new = jnp.maximum(m_acc, m_blk)
        c_acc = jnp.exp(m_acc - m_new)
        c_blk = jnp.exp(m_blk - m_new)
        o_acc = (o_acc * jnp.moveaxis(c_acc, 1, 2)
                 + o_blk * jnp.moveaxis(c_blk, 1, 2))
        l_acc = l_acc * c_acc + l_blk * c_blk
        return (k_blk, v_blk, m_new, o_acc, l_acc), None

    # step 0: this device's own block seeds the accumulators
    m0, o0, l0 = _block_attn(q, k, v, mask_for(my_idx))
    (k_f, v_f, m_f, o_f, l_f), _ = jax.lax.scan(
        step, (k, v, m0, o0, l0), jnp.arange(1, n))
    del k_f, v_f, m_f
    denom = jnp.moveaxis(l_f, 1, 2)                            # [B,Tq,H,1]
    return o_f / jnp.maximum(denom, 1e-20)


def make_ring_attention(mesh: Mesh, *, causal: bool = False,
                        q_spec: Optional[P] = None):
    """Wrap ring_attention in shard_map over ``mesh``.

    Default specs: [batch->dp, seq->sp, heads->tp, head_dim] for q/k/v.
    """
    spec = q_spec or P("dp", "sp", "tp", None)
    fn = functools.partial(ring_attention, causal=causal)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
