"""Qwen3-Next decoder (Qwen/Qwen3-Next-80B-A3B): three Gated DeltaNet
linear-attention layers to one gated full-attention layer, every layer
followed by a shared expert beside top-k routed experts under a softmax
router that normalises what it chose.

Layer equations (``n*`` RMSNorm in the zero-centred form ``x / rms(x) *
(1 + w)``; the residual stream float32):

    x = x + mixer(n1(x));  x = x + moe(n2(x))

linear layer (Gated DeltaNet), Hk key heads with r = Hv / Hk value heads
each, key and value size dk, dv; ``h = n1(x)``:
    [q, k, v, z] = h W_qkvz   a key head's columns together: its q and k
                              [dk], its r value heads' v and z [r dv]
    [b, a] = h W_ba           a scalar a value head, grouped alike
    [q, k, v] = silu(conv(concat[q, k, v]))   depthwise and causal along
                the sequence, kernel K, K - 1 zeros before the first
                token, no bias
    q = l2norm(q) / sqrt(dk);  k = l2norm(k)   a head; value head j
                reads key head j // r
    beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)
    o = gated_delta_rule(q, k, v, g, beta)     ``ops/gated_delta.py``
    y = rmsnorm(o) * w * silu(z)   a head, plain weight
    mixer = y W_out
full layer, H query heads in groups over KV key/value heads of size hd:
    [q, gate] = h W_q   a head's q and gate [hd] together
    k, v = h W_k, h W_v;  q, k = qnorm(q), knorm(k)  a head, zero-centred
    rotary positions on the first ``partial_rotary_factor`` of a head
    o = softmax(q k^T / sqrt(hd)) v, causal;  mixer = (o * sigmoid(gate)) W_o
moe, ``m = n2(x)``:
    p = softmax(m W_r) over ALL experts; the top-k by p;
    w_e = p_e / sum_{chosen} p
    moe = sigmoid(m w_s) * shared(m) + sum_{chosen e held here} w_e expert_e(m)
    (shared and experts SiLU-gated)
logits = norm(x) W_head

The model is one rank's share of a tensor- and expert-parallel layout:
it takes the contiguous ranges of key/value heads with their query
groups (full layers), of key heads with their value heads (linear
layers), of experts and the vocabulary rows held here. Heads are
independent until the output projection sums them and experts until
the combine, so the rank computes its heads' part of the mixer and its
experts' terms; what other ranks would add is left out and nothing
stands in for it. Norms, router, shared expert and its gate are whole.

Precision: parameters float32, matmul operands in ``compute_dtype``;
float32 for the residual stream, every norm's statistics, the
convolution, the decays and write strengths, the recurrence's state and
solve, the attention scores and their softmax, the logits, and
everything that decides routing (``n2``, the router product at
``highest``, softmax, top-k).

Memory: one recomputation rule, the linear layers'. The gated delta
rule's core sits under a ``jax.checkpoint`` with the policy
``ops.gated_delta.keeps``: kept a layer and pass are its inputs (q, k a
KEY head, v, g, beta) and what the solve and the chain's kernel hand
the way back (0.17 GB at 4,096 tokens and 16 value heads); the
chunk-local products, decays and layout changes are computed again.
Keeping those too, 0.3 GB a layer more, does not fit the one-chip cell
(``PERF.md`` section 4 has the readings). The routed experts carry no
checkpoint: ``moe.sparse_dispatch`` moves only the held rows and keeps
nothing a tile by itself.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from geomx_tpu.models.moe import (gated_experts, next_token_loss,
                                  sparse_dispatch)
from geomx_tpu.models.transformer import (HIGHEST, RMSNorm,
                                          gated_attention,
                                          kernel_score_entries,
                                          rotary_frequencies,
                                          score_entries)
from geomx_tpu.ops import gated_delta
from geomx_tpu.ops.gated_delta import chunks_of, gated_delta_rule

__all__ = ["Qwen3Next", "Qwen3NextBlock", "GatedDeltaNet", "causal_conv",
           "next_token_loss"]

LINEAR = "linear_attention"     # a layer of any other kind is full
L2_EPS = 1e-6


ZeroCentredRMSNorm = partial(RMSNorm, zero_centred=True)


def causal_conv(x, kernel):
    """Depthwise convolution along the sequence: ``x`` [B, T, C],
    ``kernel`` [K, C]; ``y_t = sum_j kernel[j] * x_{t - (K - 1) + j}``,
    zeros before the first token, so ``y_t`` reads no later token."""
    with jax.named_scope("causal_conv"):
        taps, t = kernel.shape[0], x.shape[1]
        x = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        return sum(x[:, j:j + t] * kernel[j] for j in range(taps))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _heads_held(outer: Tuple[int, int], inner: Tuple[int, int], what: str):
    """(outer heads held, inner heads a head of them) where ``inner``
    is exactly the groups of ``outer``."""
    n = outer[1] - outer[0]
    group = (inner[1] - inner[0]) // n
    if inner[0] != outer[0] * group or inner[1] - inner[0] != n * group:
        raise ValueError(f"{what} heads {inner} are not the groups of "
                         f"the heads {outer} they read")
    return n, group


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer on the layer's normed input."""
    dim: int
    key_dim: int
    value_dim: int
    key_heads: Tuple[int, int]      # held here
    value_heads: Tuple[int, int]
    conv_kernel: int
    eps: float = 1e-6
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        dt = self.compute_dtype
        b, t, _ = h.shape
        dk, dv = self.key_dim, self.value_dim
        hk, r = _heads_held(self.key_heads, self.value_heads, "value")
        hv = hk * r
        dense = partial(nn.Dense, use_bias=False, dtype=dt)
        with jax.named_scope("linear_attention"):
            qkvz = dense(hk * (2 * dk + 2 * r * dv), name="in_proj_qkvz")(
                h).reshape(b, t, hk, -1)
            # what decides the decay and the write strength is float32,
            # as what decides routing is: g reaches tens a token, and a
            # product rounded to 8 bits would move it by a tenth
            ba = nn.Dense(hk * 2 * r, use_bias=False, dtype=jnp.float32,
                          precision=HIGHEST, name="in_proj_ba")(
                              h).reshape(b, t, hk, 2 * r)
            q, k, v, z = jnp.split(
                qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
            write, a = (x.reshape(b, t, hv) for x in jnp.split(ba, 2, -1))
            mixed = jnp.concatenate(
                [x.reshape(b, t, -1) for x in (q, k, v)], -1)
            mixed = nn.silu(causal_conv(
                mixed.astype(jnp.float32), self.param(
                    "conv", nn.initializers.lecun_normal(),
                    (self.conv_kernel, mixed.shape[-1]), jnp.float32)))
            q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
            q = _l2norm(q.reshape(b, t, hk, dk)) / math.sqrt(dk)
            k = _l2norm(k.reshape(b, t, hk, dk))
            a_log = self.param(
                "A_log", lambda key, shape: jnp.log(16.0 * (
                    1.0 - jax.random.uniform(key, shape))), (hv,))
            dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,))
            g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)

            # the chunk-parallel part is computed again on the way
            # back (its products, decays and layout changes are 0.3 GB a
            # layer); what the solve and the chain hand the way back
            # stays, so neither runs twice (the module's ``Memory``)
            @partial(jax.checkpoint, policy=gated_delta.keeps)
            def rule(q, k, v, g, beta):
                with jax.named_scope("gated_delta_rule"):
                    return gated_delta_rule(
                        jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2),
                        v, g, beta, dtype=dt)[0]

            o = rule(q, k, v.reshape(b, t, hv, dv), g, nn.sigmoid(write))
            y = RMSNorm(self.eps, jnp.float32, name="norm")(o) * nn.silu(
                z.reshape(b, t, hv, dv).astype(jnp.float32))
            return dense(self.dim, name="out_proj")(
                y.reshape(b, t, hv * dv))


class Qwen3NextBlock(nn.Module):
    dim: int
    kind: str                   # "linear_attention" | "full_attention"
    head_dim: int
    query_heads: Tuple[int, int]        # held here, full layers
    key_value_heads: Tuple[int, int]
    rope: Any                   # rope_type, rope_theta, partial_rotary_factor
    linear_key_dim: int
    linear_value_dim: int
    linear_key_heads: Tuple[int, int]   # held here, linear layers
    linear_value_heads: Tuple[int, int]
    conv_kernel: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    shared_width: int
    local_experts: Tuple[int, int]
    eps: float = 1e-6
    compute_dtype: Any = jnp.float32

    def _gated_ffn(self, h, width: int, prefix: str):
        dense = partial(nn.Dense, use_bias=False, dtype=self.compute_dtype)
        a = nn.silu(dense(width, name=prefix + "gate")(h)) \
            * dense(width, name=prefix + "up")(h)
        return dense(self.dim, name=prefix + "down")(a)

    def _full_attention(self, h):
        dt = self.compute_dtype
        b, t, _ = h.shape
        hd = self.head_dim
        kv, group = _heads_held(self.key_value_heads, self.query_heads,
                                "query")
        dense = partial(nn.Dense, use_bias=False, dtype=dt)
        with jax.named_scope("attention_full"):
            q, gate = jnp.split(dense(kv * group * 2 * hd, name="q_proj")(
                h).reshape(b, t, kv, group, 2 * hd), 2, axis=-1)
            k, v = (dense(kv * hd, name=n)(h).reshape(b, t, kv, hd)
                    for n in ("k_proj", "v_proj"))
            q = ZeroCentredRMSNorm(self.eps, dt, name="q_norm")(q)
            k = ZeroCentredRMSNorm(self.eps, dt, name="k_norm")(k)
            o = gated_attention(q, k, v, gate.reshape(b, t, -1),
                                *rotary_frequencies(self.rope, hd))
            return dense(self.dim, name="o_proj")(o)

    @nn.compact
    def __call__(self, x):
        """``x`` [B, T, D] float32 -> (x', rows routed to the held
        experts)."""
        dt = self.compute_dtype
        b, t, d = x.shape
        h = ZeroCentredRMSNorm(self.eps, dt, name="n1")(x)
        if self.kind == LINEAR:
            mixed = GatedDeltaNet(
                d, self.linear_key_dim, self.linear_value_dim,
                self.linear_key_heads, self.linear_value_heads,
                self.conv_kernel, self.eps, dt, name="linear_attn")(h)
        else:
            mixed = self._full_attention(h)
        x = x + mixed.astype(jnp.float32)
        m = ZeroCentredRMSNorm(self.eps, jnp.float32, name="n2")(x)
        with jax.named_scope("router"):
            probs = jax.nn.softmax(nn.Dense(
                self.num_experts, use_bias=False, dtype=jnp.float32,
                precision=HIGHEST, name="router")(m), axis=-1)
            chosen_p, chosen = jax.lax.top_k(probs, self.experts_per_token)
            weights = chosen_p / jnp.sum(chosen_p, -1, keepdims=True)
        with jax.named_scope("shared_expert"):
            y = self._gated_ffn(m, self.shared_width, "shared_")
            y = y.astype(jnp.float32) * nn.sigmoid(nn.Dense(
                1, use_bias=False, dtype=dt, name="shared_expert_gate")(
                    m).astype(jnp.float32))
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
        y = y + routed.reshape(b, t, d).astype(jnp.float32)
        return x + y, jnp.sum(group_sizes)


class Qwen3Next(nn.Module):
    vocab: int
    dim: int
    layer_types: Tuple[str, ...]
    head_dim: int
    query_heads: Tuple[int, int]
    key_value_heads: Tuple[int, int]
    rope: Any
    linear_key_dim: int
    linear_value_dim: int
    linear_key_heads: Tuple[int, int]
    linear_value_heads: Tuple[int, int]
    conv_kernel: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    shared_width: int
    local_experts: Tuple[int, int]
    eps: float = 1e-6
    compute_dtype: Any = jnp.float32

    def counts(self, batch: int, t: int, kernel: bool = False):
        """What a pass over ``batch`` sequences of ``t`` positions has
        by shape: (all routed (token, slot) rows; live and computed
        score entries of the full layers' held query heads, ``kernel``:
        as the kernel computes them, its live blocks
        (``transformer.runs_kernel``); (token, value head) pairs
        through the linear layers' recurrence; the dependent chunk
        steps that takes, a sequence a loop)."""
        linear = sum(kind == LINEAR for kind in self.layer_types)
        full = len(self.layer_types) - linear
        live, computed = score_entries(t)
        if kernel:
            computed = kernel_score_entries(t, self.head_dim)
        heads = full * (self.query_heads[1] - self.query_heads[0])
        held = self.linear_value_heads[1] - self.linear_value_heads[0]
        return (batch * t * len(self.layer_types) * self.experts_per_token,
                batch * heads * live, batch * heads * computed,
                batch * t * held * linear, batch * linear * chunks_of(t))

    @nn.compact
    def __call__(self, tokens):
        """``tokens`` [B, T] -> (logits [B, T, vocab] float32, rows
        routed to the held experts summed over the layers)."""
        x = nn.Embed(self.vocab, self.dim, name="embed")(tokens)
        rows_local = 0
        for i, kind in enumerate(self.layer_types):
            x, rows = Qwen3NextBlock(
                self.dim, kind, self.head_dim, tuple(self.query_heads),
                tuple(self.key_value_heads), self.rope,
                self.linear_key_dim, self.linear_value_dim,
                tuple(self.linear_key_heads),
                tuple(self.linear_value_heads), self.conv_kernel,
                self.num_experts, self.experts_per_token,
                self.expert_width, self.shared_width,
                tuple(self.local_experts), self.eps, self.compute_dtype,
                name=f"block{i}")(x)
            rows_local = rows_local + rows
        with jax.named_scope("head"):
            x = ZeroCentredRMSNorm(self.eps, self.compute_dtype,
                                   name="norm")(x)
            logits = nn.Dense(
                self.vocab, use_bias=False, dtype=self.compute_dtype,
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32),
                name="head")(x)
        return logits, rows_local
