"""Kanana 2 decoder (kakaocorp/kanana-2-30b-a3b, ``deepseek_v3`` with no
query latent): latent attention (keys and values through one low-rank
latent, a head's rotary and non-rotary parts apart, a value head of
another size than a query/key head), a dense first layer, then layers of
a shared expert beside top-k routed experts under a sigmoid router whose
CHOICE reads a correction bias that its weights do not.

Layer equations (``n*`` RMSNorm with a learned scale; the residual
stream float32; H heads of Dn non-rotary + Dr rotary query/key dims and
Dv value dims; HF ``modeling_deepseek_v3``, ``q_lora_rank`` null):

    a = n1(x);  q = a Wq [H x (Dn + Dr)] = (q_nope, q_rope) a head
    (c, k_rope) = a Wkv_a [rank + Dr];  c = n_kv(c)
    (k_nope, v) = c Wkv_b [H x (Dn + Dv)], a head
    rotary positions on all Dr dims of q_rope and of the ONE k_rope
        every head shares, the pairs neighbours (2i, 2i + 1), plain
        frequencies theta^(-2i/Dr), no scaling
    o_h = softmax([q_nope, q_rope]_h [k_nope_h, k_rope]^T
                  / sqrt(Dn + Dr), key j <= query i) v_h
    h' = x + o Wo;  m = n2(h')
    dense layer:  y = h' + Wd(silu(Wg_f m) * (Wu_f m))
    sparse layer: s = sigmoid(m Wr) over ALL experts
                  the top-k by s + b   (b: ``e_score_correction_bias``)
                  w_e = routed_scale * s_e / (sum_{chosen} s + 1e-20)
                  y = h' + shared(m) + sum_{chosen e held here} w_e expert_e(m)
                  (shared and experts SiLU-gated)
    logits = norm(x) Whead

``b`` is no parameter: the published balance rule moves it outside the
loss (``noaux_tc``), no gradient reaches it, and it lives in the
variable collection ``buffers`` (zeros from ``init``; a caller hands in
a checkpoint's), so a trainer that takes the ``params`` leaves never
sees it.

The model is one rank's share of a tensor- and expert-parallel layout,
as ``models/laguna.py`` takes it, but heads have no key/value groups to
be cut by: the latent and the rotary key are computed alike on every
rank (``Wkv_a``, ``n_kv`` whole), and the contiguous range of heads
held here (``heads``) cuts ``Wq`` and ``Wkv_b`` by columns and ``Wo`` by
rows. The rank computes its heads' part of ``o Wo`` and its experts'
terms; what other ranks would add is left out and nothing stands in for
it. Norms, router, shared expert and the dense layer's FFN are whole.

Memory: every block is computed again on the way back (``nn.remat``:
a block keeps its input and nothing else), so inside a block nothing
has a checkpoint of its own but a dense attention core
(``transformer.causal_core``'s rule). It is the plan that fits: XLA
reads ONE trainer's fused step at the published widths, four sequences
of 8,192 tokens, at 7.82 GB of temporaries with plain blocks (4.29 GB
in ``grad_step`` alone: a pass keeps 0.5 GB a layer and 1.5 GB of
logits) and at 4.82 GB with rematerialised ones, beside 10.08 GB of
state for two trainers on a chip that gives a program 16.9 (PERF.md
section 4).

Precision: parameters float32, matmul operands in ``compute_dtype``;
float32 for the residual stream, every norm's statistics, the attention
scores and their softmax, the logits, and everything that decides
routing (``n2``, the router product at ``highest``, sigmoid, bias,
top-k, the weights' normalisation).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from geomx_tpu.models.moe import (biased_sigmoid_router, gated_experts,
                                  next_token_loss, sparse_dispatch)
from geomx_tpu.models.transformer import (RMSNorm, kernel_score_entries,
                                          latent_attention,
                                          rotary_frequencies, score_entries)

__all__ = ["Kanana", "KananaBlock", "next_token_loss"]


class KananaBlock(nn.Module):
    dim: int
    nope_dim: int               # Dn: a query/key head's non-rotary dims
    rope_dim: int               # Dr: its rotary dims
    value_dim: int              # Dv
    latent_rank: int
    heads: Tuple[int, int]      # held here, of the layer's H
    rope_theta: float
    sparse: bool
    dense_width: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    shared_width: int
    local_experts: Tuple[int, int]
    routed_scale: float
    eps: float = 1e-6
    compute_dtype: Any = jnp.float32

    def _gated_ffn(self, h, width: int, prefix: str):
        dense = partial(nn.Dense, use_bias=False, dtype=self.compute_dtype)
        a = nn.silu(dense(width, name=prefix + "gate")(h)) \
            * dense(width, name=prefix + "up")(h)
        return dense(self.dim, name=prefix + "down")(a)

    @nn.compact
    def __call__(self, x):
        """``x`` [B, T, D] float32 -> (x', rows routed to the held
        experts: 0 in the dense layer)."""
        dt = self.compute_dtype
        b, t, d = x.shape
        dn, dr, dv = self.nope_dim, self.rope_dim, self.value_dim
        heads = self.heads[1] - self.heads[0]
        dense = partial(nn.Dense, use_bias=False, dtype=dt)
        with jax.named_scope("attention_latent"):
            h = RMSNorm(self.eps, dt, name="n1")(x)
            q = dense(heads * (dn + dr), name="q")(h).reshape(
                b, t, heads, dn + dr)
            latent = dense(self.latent_rank + dr, name="kv_a")(h)
            c = RMSNorm(self.eps, dt, name="kv_norm")(
                latent[..., :self.latent_rank])
            kv = dense(heads * (dn + dv), name="kv_b")(c).reshape(
                b, t, heads, dn + dv)
            inv_freq, _ = rotary_frequencies(
                dict(rope_type="default", rope_theta=self.rope_theta), dr)
            o = latent_attention(
                q[..., :dn], q[..., dn:], kv[..., :dn],
                latent[..., self.latent_rank:], kv[..., dn:], inv_freq)
            x = x + dense(d, name="o")(o.reshape(b, t, heads * dv))
        m = RMSNorm(self.eps, jnp.float32, name="n2")(x)
        if not self.sparse:
            with jax.named_scope("dense_ffn"):
                y = self._gated_ffn(m, self.dense_width, "ffn_")
            return x + y.astype(jnp.float32), jnp.zeros((), jnp.int32)
        chosen, weights = biased_sigmoid_router(
            self, m, self.num_experts, self.experts_per_token,
            self.routed_scale)
        with jax.named_scope("shared_expert"):
            y = self._gated_ffn(m, self.shared_width, "shared_")
        held = self.local_experts[1] - self.local_experts[0]
        init = nn.initializers.lecun_normal()
        w_gate, w_up = (
            self.param(n, init, (held, d, self.expert_width),
                       jnp.float32).astype(dt) for n in ("w_gate", "w_up"))
        w_down = self.param("w_down", init, (held, self.expert_width, d),
                            jnp.float32).astype(dt)
        routed, group_sizes = sparse_dispatch(
            m.reshape(b * t, d).astype(dt), chosen.reshape(b * t, -1),
            weights.reshape(b * t, -1), gated_experts(w_gate, w_up, w_down),
            self.local_experts, self.num_experts)
        y = y.astype(jnp.float32) + routed.reshape(b, t, d).astype(
            jnp.float32)
        return x + y, jnp.sum(group_sizes)


class Kanana(nn.Module):
    vocab: int
    dim: int
    depth: int
    dense_layers: int           # the leading layers with a dense FFN
    nope_dim: int
    rope_dim: int
    value_dim: int
    latent_rank: int
    heads: Tuple[int, int]
    rope_theta: float
    dense_width: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    shared_width: int
    local_experts: Tuple[int, int]
    routed_scale: float
    eps: float = 1e-6
    compute_dtype: Any = jnp.float32

    def counts(self, batch: int, t: int, kernel: bool = False):
        """What a pass over ``batch`` sequences of ``t`` positions has
        by shape: (all routed (token, slot) rows, live score entries,
        computed score entries), the entries over all layers and held
        heads; ``kernel``: the cores run as the kernel
        (``transformer.runs_kernel``), which computes its live blocks at
        the blocks a query/key head of ``nope_dim + rope_dim`` gets."""
        live, computed = score_entries(t)
        if kernel:
            computed = kernel_score_entries(t, self.nope_dim + self.rope_dim)
        cores = batch * self.depth * (self.heads[1] - self.heads[0])
        return (batch * t * (self.depth - self.dense_layers)
                * self.experts_per_token, cores * live, cores * computed)

    @nn.compact
    def __call__(self, tokens):
        """``tokens`` [B, T] -> (logits [B, T, vocab] float32, rows
        routed to the held experts summed over the sparse layers)."""
        x = nn.Embed(self.vocab, self.dim, name="embed")(tokens)
        rows_local = 0
        block = nn.remat(KananaBlock)
        for i in range(self.depth):
            x, rows = block(
                self.dim, self.nope_dim, self.rope_dim, self.value_dim,
                self.latent_rank, tuple(self.heads), self.rope_theta,
                i >= self.dense_layers, self.dense_width, self.num_experts,
                self.experts_per_token, self.expert_width,
                self.shared_width, tuple(self.local_experts),
                self.routed_scale, self.eps, self.compute_dtype,
                name=f"block{i}")(x)
            rows_local = rows_local + rows
        with jax.named_scope("head"):
            x = RMSNorm(self.eps, self.compute_dtype, name="norm")(x)
            logits = nn.Dense(
                self.vocab, use_bias=False, dtype=self.compute_dtype,
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32),
                name="head")(x)
        return logits, rows_local
