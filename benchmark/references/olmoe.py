"""Plain reference for the ``olmoe`` family: one rank's share of an
OLMoE decoder (allenai/OLMoE-1B-7B) in straightforward ``jax.numpy``,
float32, matmuls at ``highest``.

No flax, no sort, no grouped matmul, nothing taken from the program:
the experts are a loop over the experts held here with a mask, the
attention is the dense ``[T, T]`` product. The weights are made here
from the seed (:func:`init_params`) under the path names the program's
parameter tree happens to use, and handed to both sides.

Equations (HF ``modeling_olmoe.py``; ``n*`` RMSNorm with a learned
scale, eps ``rms_norm_eps``):
    x   = wte[tokens]
    per layer:
      a = n1(x); q = qnorm(a Wq); k = knorm(a Wk); v = a Wv
          (the two norms over the whole projection, before the head
          split); rotary positions on q and k (half-split layout, theta
          ``rope_theta``); causal softmax attention per head, scale
          1/sqrt(head); h = x + o Wo
      m = n2(h); p = softmax(m Wr) over ALL ``num_experts``; the
          ``num_experts_per_tok`` largest p_e, not renormalised
      y = h + sum over the chosen e in ``local_experts`` of
          p_e * (silu(m Wg_e) * (m Wu_e)) Wd_e
    logits = norm(x) Whead            (the vocabulary rows held here)
    loss of a sequence = mean next-token cross-entropy
          + ``router_aux_loss_coef`` * E * sum_e f_e P_e
      (HF ``load_balancing_loss_func``: f_e the (token, slot)
      assignments to e over the layers' tokens, slots summed; P_e the
      mean router probability; layers pooled)
    loss = mean over the sequences

The share: the router keeps its published width and its top-k of all
experts; only the terms of the experts in ``local_experts`` are
computed, and nothing stands in for the others. Departures from the
published training recipe, mirrored from the program: the auxiliary
loss is taken per sequence (HF pools the batch), the paper's router
z-loss is left out, no dropout.

``operand_dtype`` is the control of ``correct``: the same mathematics
with every operand of a matmul that the configuration runs in its
compute dtype rounded to that type first (an 8-bit float with a scale
per tensor). The router product is float32 in the configuration and
stays so in the control. ``None`` is the reference itself.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# the rounded-operand matmul of the control is the families' common one
from benchmark.references.transformer import HIGHEST, _mm

INIT_STD = 0.02     # OlmoeConfig's initializer_range


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    d, w, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    lo, hi = cfg["local_experts"]
    shapes = {"embed/embedding": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"block{i}/"
        shapes.update({
            b + "n1/scale": (d,),
            b + "q/kernel": (d, d), b + "k/kernel": (d, d),
            b + "v/kernel": (d, d), b + "o/kernel": (d, d),
            b + "q_norm/scale": (d,), b + "k_norm/scale": (d,),
            b + "n2/scale": (d,),
            b + "router/kernel": (d, cfg["num_experts"]),
            b + "w_gate": (hi - lo, d, w), b + "w_up": (hi - lo, d, w),
            b + "w_down": (hi - lo, w, d)})
    shapes.update({"norm/scale": (d,), "head/kernel": (d, v)})
    return shapes


def num_params(cfg: dict) -> int:
    n = 0
    for shape in param_shapes(cfg).values():
        size = 1
        for s in shape:
            size *= s
        n += size
    return n


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight from the seed in ONE jitted call on the default
    device, float32: matrices, expert stacks and the embedding
    normal(0, 0.02), RMSNorm scales 1."""
    shapes = param_shapes(cfg)

    def make(key):
        return {name: (jnp.ones(shape, jnp.float32)
                       if name.endswith("/scale") else
                       INIT_STD * jax.random.normal(
                           jax.random.fold_in(key, i), shape, jnp.float32))
                for i, (name, shape) in enumerate(shapes.items())}

    # a seed may exceed 32 signed bits: fold it in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return jax.jit(make)(key)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """[T, heads, head_dim], HF's layout: the two halves of a head are
    the pairs' first and second members."""
    t, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def sequence_loss(params: Dict[str, jax.Array], toks, cfg: dict,
                  operand_dtype: Optional[str] = None):
    """The loss of ONE sequence, ``toks`` [T+1] int32."""
    od = None if operand_dtype is None else jnp.dtype(operand_dtype)
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    n_exp, top = cfg["num_experts"], cfg["num_experts_per_tok"]
    lo, hi = cfg["local_experts"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    tokens, nxt = toks[:-1], toks[1:]
    t = tokens.shape[0]
    x = params["embed/embedding"][tokens]
    causal = jnp.tril(jnp.ones((t, t), bool))
    assigned = jnp.zeros((n_exp,), jnp.float32)
    prob_sum = jnp.zeros((n_exp,), jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        b = f"block{i}/"
        a = _rms_norm(x, params[b + "n1/scale"], eps)
        q = _rms_norm(_mm("td,de->te", a, params[b + "q/kernel"], od),
                      params[b + "q_norm/scale"], eps)
        k = _rms_norm(_mm("td,de->te", a, params[b + "k/kernel"], od),
                      params[b + "k_norm/scale"], eps)
        v = _mm("td,de->te", a, params[b + "v/kernel"], od)
        q, k, v = (z.reshape(t, heads, d // heads) for z in (q, k, v))
        s = _mm("qhd,khd->hqk", _rope(q, theta), _rope(k, theta), od) \
            / jnp.sqrt(jnp.float32(d // heads))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = _mm("hqk,khd->qhd", p, v, od).reshape(t, d)
        x = x + _mm("td,de->te", o, params[b + "o/kernel"], od)
        m = _rms_norm(x, params[b + "n2/scale"], eps)
        probs = jax.nn.softmax(
            jnp.einsum("td,de->te", m, params[b + "router/kernel"],
                       precision=HIGHEST), axis=-1)
        gates, chosen = jax.lax.top_k(probs, top)
        assigned = assigned + jax.nn.one_hot(
            chosen, n_exp, dtype=jnp.float32).sum((0, 1))
        prob_sum = prob_sum + probs.sum(0)
        y = jnp.zeros_like(x)
        for j, e in enumerate(range(lo, hi)):
            weight = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)
            act = jax.nn.silu(_mm("td,dw->tw", m, params[b + "w_gate"][j],
                                  od)) \
                * _mm("td,dw->tw", m, params[b + "w_up"][j], od)
            y = y + weight[:, None] * _mm("tw,wd->td", act,
                                          params[b + "w_down"][j], od)
        x = x + y
    x = _rms_norm(x, params["norm/scale"], eps)
    logp = jax.nn.log_softmax(
        _mm("td,dv->tv", x, params["head/kernel"], od), axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, nxt[:, None], axis=-1))
    rows = cfg["num_hidden_layers"] * t
    aux = n_exp * jnp.sum((assigned / rows) * (prob_sum / rows))
    return ce + cfg["router_aux_loss_coef"] * aux


def loss_and_grads(params, toks, cfg: dict,
                   operand_dtype: Optional[str] = None):
    """``toks`` [B, T+1]: the mean over the sequences of each one's
    loss and gradient, a sequence at a time (the loss is defined per
    sequence, and one sequence of 4,096 tokens in float32 is what the
    chip holds beside the weights)."""
    grad = jax.value_and_grad(sequence_loss)

    def add(total, seq):
        return jax.tree_util.tree_map(
            jnp.add, total, grad(params, seq, cfg, operand_dtype)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, params))
    total, _ = jax.lax.scan(add, zero, toks)
    return jax.tree_util.tree_map(lambda s: s / toks.shape[0], total)


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward pass of THIS SHARE requires per token at
    sequence length T (multiply-add = 2):
        per layer   q, k, v, o: 4 * 2*D*D; attention QK^T and PV over
                    the causal context, whose mean length is (T+1)/2:
                    4*D*(T+1)/2; router 2*D*E; experts: the rows a token
                    sends to the experts held here, at their EXPECTED
                    number under even routing, k * E_local / E, each
                    3 * 2*D*W (gate, up, down)
        head        2*D*V over the vocabulary rows held here
    Attention and head are counted in full, as this rank computes them.
    Under skewed routing the rows routed here differ from the
    expectation: ``moe.local_row_share`` reports them, and the count
    stays what even routing requires. Lookups, norms, rotary positions,
    softmax, SiLU and the combine are not counted."""
    d, w, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    lo, hi = cfg["local_experts"]
    rows = cfg["num_experts_per_tok"] * (hi - lo) / cfg["num_experts"]
    layer = (8 * d * d + 4 * d * (seq_len + 1) / 2
             + 2 * d * cfg["num_experts"] + rows * 6 * d * w)
    return cfg["num_hidden_layers"] * layer + 2 * d * v


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (twice forward); nothing recomputed counts."""
    return 3 * forward_flops_per_token(cfg, seq_len)
