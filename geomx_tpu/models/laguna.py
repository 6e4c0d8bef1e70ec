"""Laguna decoder (poolside/Laguna-XS.2): full and sliding-window
attention layers with different numbers of query heads over shared
key/value heads, a sigmoid gate on the attention output, a dense first
layer, then layers of a shared expert beside top-k routed experts under
a sigmoid router.

Layer equations (``n*`` RMSNorm with a learned scale; the residual
stream float32). Layer l is ``full`` or ``sliding`` and has H_l query
heads in groups of G_l = H_l / KV over the KV key/value heads:

    a = n1(x);  q = a Wq [H_l x hd];  k, v = a Wk, a Wv [KV x hd]
    g = sigmoid(a Wg) [H_l x hd]
    full:    rotary positions on the first ``partial_rotary_factor`` of
             a head's dims, YaRN frequencies (HF
             ``_compute_yarn_parameters``), cos and sin times the
             attention factor; key j <= query i
    sliding: rotary positions on all dims, plain frequencies; key j
             with 0 <= i - j < window
    o_h = softmax(q_h k_{h // G_l}^T / sqrt(hd)) v_{h // G_l}
    h' = x + (o * g) Wo;  m = n2(h')
    dense layer:  y = h' + Wd(silu(Wg_f m) * (Wu_f m))
    sparse layer: s = sigmoid(m Wr) over ALL experts; the top-k by s;
                  w_e = routed_scale * s_e / sum_{chosen} s
                  y = h' + shared(m) + sum_{chosen e held here} w_e expert_e(m)
                  (shared and experts SiLU-gated)
    logits = norm(x) Whead

The model is one rank's share of a tensor- and expert-parallel layout:
it takes the contiguous ranges of query heads (per layer) and key/value
heads, of experts (``local_experts``) and the vocabulary rows
(``vocab``) held here. Heads are independent until ``Wo`` sums them and
experts until the combine, so the rank computes its heads' part of
``(o * g) Wo`` and its experts' terms; what other ranks would add is
left out and nothing stands in for it. Norms, router, shared expert and
the dense layer's FFN are whole.

Precision: parameters float32, matmul operands in ``compute_dtype``;
float32 for the residual stream, every norm's statistics, the attention
scores and their softmax, the logits, and everything that decides
routing (``n2``, the router product at ``highest``, sigmoid, top-k).

Memory: no block and no part of one carries a checkpoint of its own.
A dense attention core is computed again on the way back and the
kernels keep q, k, v, o and the log-sum-exp (``models/transformer.py``);
``moe.sparse_dispatch`` moves only the held rows and keeps nothing a
tile by itself.
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
                                          gated_attention,
                                          kernel_score_entries,
                                          kernel_window_score_entries,
                                          rotary_frequencies,
                                          score_entries)

__all__ = ["Laguna", "LagunaBlock", "next_token_loss",
           "rotary_frequencies"]


class LagunaBlock(nn.Module):
    dim: int
    head_dim: int
    kind: str                   # "full_attention" | "sliding_attention"
    query_heads: Tuple[int, int]    # held here, of this layer's H_l
    key_value_heads: Tuple[int, int]
    window: int
    rope: Any                       # this kind's block of rope_parameters
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
            gate = dense(heads * hd, name="gate")(h)
            x = x + dense(d, name="o")(gated_attention(
                q, k, v, gate, *rotary_frequencies(self.rope, hd),
                window=None if full else self.window))
        m = RMSNorm(self.eps, jnp.float32, name="n2")(x)
        if not self.sparse:
            with jax.named_scope("dense_ffn"):
                y = self._gated_ffn(m, self.dense_width, "ffn_")
            return x + y.astype(jnp.float32), jnp.zeros((), jnp.int32)
        with jax.named_scope("router"):
            scores = nn.sigmoid(nn.Dense(
                self.num_experts, use_bias=False, dtype=jnp.float32,
                precision=HIGHEST, name="router")(m))
            chosen_s, chosen = jax.lax.top_k(scores, self.experts_per_token)
            weights = self.routed_scale * chosen_s / jnp.sum(
                chosen_s, -1, keepdims=True)
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


class Laguna(nn.Module):
    vocab: int
    dim: int
    head_dim: int
    layer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]        # "dense" | "sparse"
    query_heads: Tuple[Tuple[int, int], ...]    # held here, per layer
    key_value_heads: Tuple[int, int]
    window: int
    rope: Any                   # rope_parameters: a block per layer type
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
        query heads; ``kernel``: the layers run as the kernels
        (``transformer.runs_kernel``; the sliding ones from its floor on
        the window), which compute their live blocks."""
        live = computed = 0
        for kind, (lo, hi) in zip(self.layer_types, self.query_heads):
            a, c = score_entries(t, None if kind == FULL else self.window)
            if kernel and kind == FULL:
                c = kernel_score_entries(t, self.head_dim)
            elif kernel:
                c = kernel_window_score_entries(t, self.window,
                                                self.head_dim)
            live, computed = live + (hi - lo) * a, computed + (hi - lo) * c
        sparse = sum(m == "sparse" for m in self.mlp_layer_types)
        return (batch * t * sparse * self.experts_per_token,
                batch * live, batch * computed)

    @nn.compact
    def __call__(self, tokens):
        """``tokens`` [B, T] -> (logits [B, T, vocab] float32, rows
        routed to the held experts summed over the sparse layers)."""
        x = nn.Embed(self.vocab, self.dim, name="embed")(tokens)
        rows_local = 0
        for i, (kind, mlp) in enumerate(zip(self.layer_types,
                                            self.mlp_layer_types)):
            x, rows = LagunaBlock(
                self.dim, self.head_dim, kind, tuple(self.query_heads[i]),
                tuple(self.key_value_heads), self.window, self.rope[kind],
                mlp == "sparse", self.dense_width, self.num_experts,
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
