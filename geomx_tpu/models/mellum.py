"""Mellum 2 decoder (JetBrains/Mellum2-12B-A2.5B): three sliding-window
attention layers to one full layer, grouped queries, rotary positions on
the whole head (YaRN in the full layers only), and in EVERY layer top-k
routed SiLU-gated experts under a softmax router that normalises what it
chose; no dense FFN, no shared expert, no gate on the attention output.

Layer equations (``n*`` RMSNorm with a learned scale; the residual
stream float32). A layer is ``full`` or ``sliding`` and has H query
heads in groups of G = H / KV over the KV key/value heads:

    a = n1(x);  q = a Wq [H x hd];  k, v = a Wk, a Wv [KV x hd]
    sliding: rotary positions on all dims, plain frequencies; key j
             with 0 <= i - j < window
    full:    rotary positions on all dims, YaRN frequencies (HF
             ``_compute_yarn_parameters``), cos and sin times the
             attention factor; key j <= query i
    o_h = softmax(q_h k_{h // G}^T / sqrt(hd)) v_{h // G}
    h' = x + o Wo;  m = n2(h')
    p = softmax(m Wr) over ALL experts; the top-k by p;
    w_e = p_e / sum_{chosen} p
    y = h' + sum_{chosen e held here} w_e expert_e(m)
    logits = norm(x) Whead

The model is one rank's share of a tensor- and expert-parallel layout,
taken as ``models/laguna.py`` takes it: the contiguous ranges of query
and key/value heads, of experts (``local_experts``) and the vocabulary
rows (``vocab``) held here. The rank computes its heads' part of
``o Wo`` and its experts' terms; what other ranks would add is left out
and nothing stands in for it. Norms and router are whole.

Memory: a block keeps what its matmuls produced; only a dense attention
core (``transformer.rotary_attention``'s rule) and the dispatch's tiles
(``moe.sparse_dispatch``) are computed again on the way back. A pass of
8,192 tokens through four such blocks takes 2.6 GB of temporaries by
XLA's reading, and the fused step's peak lies in its flat-sized tail,
not here: rematerialising whole blocks bought nothing there and cost
6% of the tokens (PERF.md section 4).

Precision: parameters float32, matmul operands in ``compute_dtype``;
float32 for the residual stream, every norm's statistics, the attention
scores and their softmax, the logits, and everything that decides
routing (``n2``, the router product at ``highest``, softmax, top-k, the
weights' normalisation).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from geomx_tpu.models.moe import (gated_experts, next_token_loss,
                                  sparse_dispatch)
from geomx_tpu.models.transformer import (FULL, HIGHEST, RMSNorm,
                                          kernel_score_entries,
                                          kernel_window_score_entries,
                                          rotary_attention,
                                          rotary_frequencies, score_entries)

__all__ = ["Mellum", "MellumBlock", "next_token_loss"]


class MellumBlock(nn.Module):
    dim: int
    head_dim: int
    kind: str                   # "full_attention" | "sliding_attention"
    query_heads: Tuple[int, int]    # held here, of the layer's H
    key_value_heads: Tuple[int, int]
    window: int
    rope: Any                       # this kind's block of rope_parameters
    num_experts: int
    experts_per_token: int
    expert_width: int
    local_experts: Tuple[int, int]
    eps: float = 1e-6
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        """``x`` [B, T, D] float32 -> (x', rows routed to the held
        experts)."""
        dt = self.compute_dtype
        b, t, d = x.shape
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
        full = self.kind == FULL
        with jax.named_scope("attention_full" if full
                             else "attention_window"):
            h = RMSNorm(self.eps, dt, name="n1")(x)
            q = dense(heads * hd, name="q")(h).reshape(b, t, kv, group, hd)
            k, v = (dense(kv * hd, name=n)(h).reshape(b, t, kv, hd)
                    for n in ("k", "v"))
            o = rotary_attention(
                q, k, v, *rotary_frequencies(self.rope, hd),
                window=None if full else self.window)
            x = x + dense(d, name="o")(o.reshape(b, t, heads * hd))
        m = RMSNorm(self.eps, jnp.float32, name="n2")(x)
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
            m.reshape(b * t, d).astype(dt), chosen.reshape(b * t, -1),
            weights.reshape(b * t, -1), gated_experts(w_gate, w_up, w_down),
            self.local_experts, self.num_experts)
        return (x + routed.reshape(b, t, d).astype(jnp.float32),
                jnp.sum(group_sizes))


class Mellum(nn.Module):
    vocab: int
    dim: int
    head_dim: int
    layer_types: Tuple[str, ...]
    query_heads: Tuple[int, int]
    key_value_heads: Tuple[int, int]
    window: int
    rope: Any                   # rope_parameters: a block per layer type
    num_experts: int
    experts_per_token: int
    expert_width: int
    local_experts: Tuple[int, int]
    eps: float = 1e-6
    compute_dtype: Any = jnp.float32

    def counts(self, batch: int, t: int, kernel: bool = False):
        """What a pass over ``batch`` sequences of ``t`` positions has
        by shape: (all routed (token, slot) rows, live score entries,
        computed score entries), the entries over all layers and held
        query heads; ``kernel``: the layers run as the kernels
        (``transformer.runs_kernel``; the sliding ones from its floor on
        the window), which compute their live blocks."""
        heads = self.query_heads[1] - self.query_heads[0]
        live = computed = 0
        for kind in self.layer_types:
            a, c = score_entries(t, None if kind == FULL else self.window)
            if kernel and kind == FULL:
                c = kernel_score_entries(t, self.head_dim)
            elif kernel:
                c = kernel_window_score_entries(t, self.window,
                                                self.head_dim)
            live, computed = live + heads * a, computed + heads * c
        return (batch * t * len(self.layer_types) * self.experts_per_token,
                batch * live, batch * computed)

    @nn.compact
    def __call__(self, tokens):
        """``tokens`` [B, T] -> (logits [B, T, vocab] float32, rows
        routed to the held experts summed over the layers)."""
        x = nn.Embed(self.vocab, self.dim, name="embed")(tokens)
        rows_local = 0
        for i, kind in enumerate(self.layer_types):
            x, rows = MellumBlock(
                self.dim, self.head_dim, kind, tuple(self.query_heads),
                tuple(self.key_value_heads), self.window, self.rope[kind],
                self.num_experts, self.experts_per_token, self.expert_width,
                tuple(self.local_experts), self.eps, self.compute_dtype,
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
