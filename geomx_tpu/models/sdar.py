"""SDAR decoder as it is TRAINED (JetLM/SDAR-30B-A3B-Chat, ``sdar_moe``):
a Qwen3-MoE stack under the masked block-diffusion objective (BD3-LMs,
Arriola et al. 2025, vectorised): every sequence goes through every
layer twice side by side, a clean copy and a copy in which positions
are replaced by a MASK token, under one block mask; the loss is taken
at the masked positions of the noised copy
(``moe.masked_diffusion_loss``).

Layer equations (``n*`` RMSNorm with a learned scale; the residual
stream float32). ``z`` [B, 2T, D] holds the clean copy at [0, T) and
the noised copy at [T, 2T); both carry the position ids 0..T-1. H query
heads in groups of G = H / KV over the KV key/value heads:

    a = n1(z);  q = a Wq [H x hd];  k, v = a Wk, a Wv [KV x hd]
    q, k = q_norm(q), k_norm(k)     RMSNorm over each head's hd dims
    rotary positions on all hd dims, BY POSITION ID
    o_h = softmax(q_h k_{h // G}^T / sqrt(hd)) v_{h // G} over the keys
          the block mask allows (``transformer.block_diffusion_mask``):
          a clean query the clean blocks up to and including its own, a
          noised query the clean blocks strictly before its own and the
          noised positions of its own block, both directions
    h' = z + o Wo;  m = n2(h')
    p = softmax(m Wr) over ALL experts; the top-k by p;
    w_e = p_e / sum_{chosen} p
    y = h' + sum_{chosen e held here} w_e expert_e(m)
    logits = norm(z[noised half]) Whead

No dense FFN, no shared expert, no window. The model is one rank's
share of a tensor- and expert-parallel layout, taken as
``models/mellum.py`` takes it: the contiguous ranges of query and
key/value heads, of experts (``local_experts``) and the vocabulary rows
(``vocab``) held here; norms (the heads' too) and router are whole.

The clean half of the LAST layer feeds nothing (no noised query of a
later layer reads it, no loss is taken there); its query, output
projection and experts run like any other layer's.

Precision: parameters float32, matmul operands in ``compute_dtype``;
float32 for the residual stream, every norm's statistics, rotary
angles, the attention scores and their softmax, the logits, and
everything that decides routing.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from geomx_tpu.models.moe import (gated_experts, masked_diffusion_loss,
                                  sparse_dispatch)
from geomx_tpu.models.transformer import (HIGHEST, RMSNorm,
                                          block_diffusion_attention,
                                          block_score_entries,
                                          kernel_block_score_entries,
                                          rotary, rotary_frequencies)

__all__ = ["Sdar", "SdarBlock", "masked_diffusion_loss"]


class SdarBlock(nn.Module):
    dim: int
    head_dim: int
    query_heads: Tuple[int, int]    # held here, of the layer's H
    key_value_heads: Tuple[int, int]
    block_length: int
    rope_theta: float
    num_experts: int
    experts_per_token: int
    expert_width: int
    local_experts: Tuple[int, int]
    eps: float = 1e-6
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, z):
        """``z`` [B, 2T, D] float32, the clean copy before the noised
        one -> (z', rows routed to the held experts)."""
        dt = self.compute_dtype
        b, t2, d = z.shape
        hd = self.head_dim
        kv = self.key_value_heads[1] - self.key_value_heads[0]
        heads = self.query_heads[1] - self.query_heads[0]
        group = heads // kv
        if (self.query_heads[0] != self.key_value_heads[0] * group
                or heads != kv * group):
            raise ValueError(
                f"query heads {self.query_heads} are not the groups of "
                f"key/value heads {self.key_value_heads}")
        dense = partial(nn.Dense, use_bias=False, dtype=dt)
        with jax.named_scope("attention_blockdiff"):
            h = RMSNorm(self.eps, dt, name="n1")(z)
            q = dense(heads * hd, name="q")(h).reshape(b, t2, kv, group, hd)
            k, v = (dense(kv * hd, name=n)(h).reshape(b, t2, kv, hd)
                    for n in ("k", "v"))
            inv_freq, factor = rotary_frequencies(
                {"rope_type": "default", "rope_theta": self.rope_theta}, hd)
            ids = jnp.tile(jnp.arange(t2 // 2, dtype=jnp.float32), 2)
            q, k = (rotary(RMSNorm(self.eps, dt, name=n)(x), inv_freq,
                           factor, positions=ids)
                    for n, x in (("q_norm", q), ("k_norm", k)))
            o = block_diffusion_attention(q, k, v, self.block_length)
            z = z + dense(d, name="o")(o.reshape(b, t2, heads * hd))
        m = RMSNorm(self.eps, jnp.float32, name="n2")(z)
        with jax.named_scope("router"):
            probs = jax.nn.softmax(nn.Dense(
                self.num_experts, use_bias=False, dtype=jnp.float32,
                precision=HIGHEST, name="router")(m), axis=-1)
            chosen_p, chosen = jax.lax.top_k(probs, self.experts_per_token)
            weights = chosen_p / jnp.sum(chosen_p, -1, keepdims=True)
        held = self.local_experts[1] - self.local_experts[0]
        init = nn.initializers.lecun_normal()
        w_gate, w_up = (
            self.param(n, init, (held, d, self.expert_width),
                       jnp.float32).astype(dt) for n in ("w_gate", "w_up"))
        w_down = self.param("w_down", init, (held, self.expert_width, d),
                            jnp.float32).astype(dt)
        routed, group_sizes = sparse_dispatch(
            m.reshape(b * t2, d).astype(dt), chosen.reshape(b * t2, -1),
            weights.reshape(b * t2, -1), gated_experts(w_gate, w_up, w_down),
            self.local_experts, self.num_experts)
        return (z + routed.reshape(b, t2, d).astype(jnp.float32),
                jnp.sum(group_sizes))


class Sdar(nn.Module):
    vocab: int                  # rows held here; the last is MASK
    dim: int
    head_dim: int
    depth: int
    query_heads: Tuple[int, int]
    key_value_heads: Tuple[int, int]
    block_length: int
    rope_theta: float
    num_experts: int
    experts_per_token: int
    expert_width: int
    local_experts: Tuple[int, int]
    eps: float = 1e-6
    compute_dtype: Any = jnp.float32

    def counts(self, batch: int, t: int, kernel: bool = False):
        """What a pass over ``batch`` sequences of ``t`` tokens (2t
        positions each) has by shape: (all routed (position, slot) rows,
        live score entries, computed score entries), the entries over
        all layers and held query heads; ``kernel``: the cores run as
        the kernel (``transformer.runs_kernel``), which computes its
        live tiles."""
        heads = self.query_heads[1] - self.query_heads[0]
        live, computed = block_score_entries(t, self.block_length)
        if kernel:
            computed = kernel_block_score_entries(t, self.block_length,
                                                  self.head_dim)
        per = batch * heads * self.depth
        return (batch * 2 * t * self.depth * self.experts_per_token,
                per * live, per * computed)

    @nn.compact
    def __call__(self, ids):
        """``ids`` [B, 2T]: the clean ids of every sequence before its
        noised ids (MASK, ``vocab - 1``, where a position is masked) ->
        (logits of the NOISED half [B, T, vocab] float32, rows routed to
        the held experts summed over the layers)."""
        z = nn.Embed(self.vocab, self.dim, name="embed")(ids)
        rows_local = 0
        for i in range(self.depth):
            z, rows = SdarBlock(
                self.dim, self.head_dim, tuple(self.query_heads),
                tuple(self.key_value_heads), self.block_length,
                self.rope_theta, self.num_experts, self.experts_per_token,
                self.expert_width, tuple(self.local_experts), self.eps,
                self.compute_dtype, name=f"block{i}")(z)
            rows_local = rows_local + rows
        with jax.named_scope("head"):
            z = RMSNorm(self.eps, self.compute_dtype, name="norm")(
                z[:, ids.shape[1] // 2:])
            logits = nn.Dense(
                self.vocab, use_bias=False, dtype=self.compute_dtype,
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32),
                name="head")(z)
        return logits, rows_local
