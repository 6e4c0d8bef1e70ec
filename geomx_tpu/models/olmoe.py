"""OLMoE decoder (allenai/OLMoE-1B-7B): RMSNorm, rotary positions,
RMSNorm on q and k, bias-free projections, a float32 router over all
experts with top-k SiLU-gated experts, no-drop sparse dispatch.

Layer equations (HF ``modeling_olmoe.py``; ``n*`` are RMSNorms with a
learned scale):

    h = x + Wo Attn(rope(qnorm(Wq n1(x))), rope(knorm(Wk n1(x))), Wv n1(x))
    p = softmax_float32(Wr n2(h))              over ALL experts
    y = h + sum_{e in topk(p), e held here} p_e Wd_e(silu(Wg_e n2(h)) * Wu_e n2(h))

``q_norm`` and ``k_norm`` run over the whole projection (before the
head split), the top-k weights are not renormalised. The model takes
``local_experts``, a contiguous range of expert ids, and ``vocab``, the
vocabulary rows held here: one rank's share of an expert- and
vocabulary-parallel layout. The router keeps its full width and its
top-k; the rank computes its own experts' terms for the rows routed to
them and nothing for the others — no code stands in for absent ranks.

Precision: parameters float32, matmul operands in ``compute_dtype``;
float32 for the residual stream, every norm's statistics, the attention
scores and their softmax, the logits, and everything that decides
routing (``n2``, the router product, softmax, top-k).

Loss (:func:`next_token_loss`): mean next-token cross-entropy plus
``aux_coef`` times HF's ``load_balancing_loss_func`` taken per sequence
(so that the loss of a batch is the mean of its sequences' losses,
whatever the microbatch).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from geomx_tpu.models.moe import gated_experts, sparse_dispatch
from geomx_tpu.models.transformer import (HIGHEST, RMSNorm,
                                          causal_attention)

__all__ = ["Olmoe", "OlmoeBlock", "next_token_loss"]


def rope(x, theta: float):
    """Rotary positions on [B, T, heads, head_dim], HF's half-split
    layout: ``x * cos + rotate_half(x) * sin``, angles in float32."""
    t, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], -1)
    return (x.astype(jnp.float32) * jnp.cos(ang)
            + rot * jnp.sin(ang)).astype(x.dtype)


class OlmoeBlock(nn.Module):
    dim: int
    heads: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    local_experts: Tuple[int, int]
    eps: float = 1e-5
    rope_theta: float = 10000.0
    attn_fn: Optional[Callable] = None
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        """``x`` [B, T, D] float32 -> (x', router probabilities
        [B, T, E], chosen experts [B, T, k], rows routed to the held
        experts)."""
        dt = self.compute_dtype
        b, t, d = x.shape
        with jax.named_scope("attention"):
            h = RMSNorm(self.eps, dt, name="n1")(x)
            q, k, v = (nn.Dense(d, use_bias=False, dtype=dt, name=n)(h)
                       for n in ("q", "k", "v"))
            q = RMSNorm(self.eps, dt, name="q_norm")(q)
            k = RMSNorm(self.eps, dt, name="k_norm")(k)
            shp = (b, t, self.heads, d // self.heads)
            attn = self.attn_fn or causal_attention
            o = attn(rope(q.reshape(shp), self.rope_theta),
                     rope(k.reshape(shp), self.rope_theta), v.reshape(shp))
            x = x + nn.Dense(d, use_bias=False, dtype=dt, name="o")(
                o.reshape(b, t, d))
        h = RMSNorm(self.eps, jnp.float32, name="n2")(x)
        with jax.named_scope("router"):
            logits = nn.Dense(self.num_experts, use_bias=False,
                              dtype=jnp.float32, precision=HIGHEST,
                              name="router")(h)
            probs = jax.nn.softmax(logits, axis=-1)
            gates, chosen = jax.lax.top_k(probs, self.experts_per_token)
        held = self.local_experts[1] - self.local_experts[0]
        init = nn.initializers.lecun_normal()
        w_gate, w_up = (
            self.param(n, init, (held, d, self.expert_width),
                       jnp.float32).astype(dt) for n in ("w_gate", "w_up"))
        w_down = self.param("w_down", init, (held, self.expert_width, d),
                            jnp.float32).astype(dt)

        y, group_sizes = sparse_dispatch(
            h.reshape(b * t, d).astype(dt),
            chosen.reshape(b * t, -1), gates.reshape(b * t, -1),
            gated_experts(w_gate, w_up, w_down), self.local_experts,
            self.num_experts)
        return (x + y.reshape(b, t, d).astype(jnp.float32), probs, chosen,
                jnp.sum(group_sizes))


class Olmoe(nn.Module):
    vocab: int
    dim: int
    depth: int
    heads: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    local_experts: Tuple[int, int]
    eps: float = 1e-5
    rope_theta: float = 10000.0
    attn_fn: Optional[Callable] = None
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        """``tokens`` [B, T] -> (logits [B, T, vocab] float32, router
        probabilities [L, B, T, E], chosen experts [L, B, T, k], rows
        routed to the held experts summed over layers)."""
        x = nn.Embed(self.vocab, self.dim, name="embed")(tokens)
        probs, chosen, rows_local = [], [], 0
        for i in range(self.depth):
            x, p, c, r = OlmoeBlock(
                self.dim, self.heads, self.num_experts,
                self.experts_per_token, self.expert_width,
                tuple(self.local_experts), self.eps, self.rope_theta,
                self.attn_fn, self.compute_dtype, name=f"block{i}")(x)
            probs.append(p)
            chosen.append(c)
            rows_local = rows_local + r
        with jax.named_scope("head"):
            x = RMSNorm(self.eps, self.compute_dtype, name="norm")(x)
            logits = nn.Dense(
                self.vocab, use_bias=False, dtype=self.compute_dtype,
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32),
                name="head")(x)
        return (logits, jnp.stack(probs),
                jnp.stack(chosen), rows_local)


def load_balancing_loss(probs, chosen):
    """HF ``load_balancing_loss_func`` per sequence: ``E * sum_e f_e
    P_e`` with ``f_e`` the (token, slot) assignments to ``e`` over the
    layers' tokens (slots summed, so the term is k at even routing) and
    ``P_e`` the mean router probability; layers pooled as HF pools
    them. ``probs`` [L, B, T, E], ``chosen`` [L, B, T, k] -> [B]."""
    e = probs.shape[-1]
    f = jnp.mean(jnp.sum(jax.nn.one_hot(chosen, e, dtype=jnp.float32), 3),
                 axis=(0, 2))
    return e * jnp.sum(f * jnp.mean(probs, axis=(0, 2)), -1)


def next_token_loss(model: Olmoe, variables, toks, aux_coef: float):
    """``toks`` [B, T+1]. Returns (loss, (rows routed to the held
    experts, all routed rows)), the counts as float32 scalars."""
    logits, probs, chosen, rows_local = model.apply(variables, toks[:, :-1])
    logp = jax.nn.log_softmax(logits)
    ce = -jnp.mean(jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1))
    loss = ce + aux_coef * jnp.mean(load_balancing_loss(probs, chosen))
    return loss, jnp.stack([rows_local.astype(jnp.float32),
                            jnp.float32(chosen.size)])
