"""Plain reference for the ``kanana`` family: one rank's share of a
Kanana 2 decoder (kakaocorp/kanana-2-30b-a3b-instruct-2601,
``model_type`` ``deepseek_v3`` with ``q_lora_rank`` null) in
straightforward ``jax.numpy``, float32, matmuls at ``highest``.

No flax, no sort, no grouped matmul, no kernel, nothing taken from the
program: the attention is the ``[T, T]`` product per held head with the
mask written out (computed a block of ``QUERY_BLOCK`` queries against
ALL keys at a time, so that 8,192 positions fit), the rotary positions
are a complex product written out on neighbouring pairs, the experts are a loop over
the experts held here with a mask. The weights are made here from the
seed (:func:`init_params`) under the path names the program's parameter
tree happens to use, and handed to both sides; the router's correction
bias is made here from the configuration (:func:`correction_bias`) and
handed to both sides too.

Equations (``n*`` RMSNorm with a learned scale, eps ``rms_norm_eps``;
Dn = ``qk_nope_head_dim``, Dr = ``qk_rope_head_dim``, Dv =
``v_head_dim``, r = ``kv_lora_rank``; HF ``modeling_deepseek_v3``):
    x = wte[tokens]
    layer l:
      a = n1(x); q = a Wq [heads x (Dn + Dr)] = (q_nope, q_rope) a head
      (c, k_rope) = a Wkv_a [r + Dr]; c = n_kv(c)
      (k_nope, v) = c Wkv_b [heads x (Dn + Dv)], a head
      rotary positions on q_rope and on the ONE k_rope all heads share:
          the pair (x_2i, x_2i+1) at position p is the complex number
          x_2i + i x_2i+1 times exp(i p theta^(-2i/Dr))
          (``rope_interleave`` true, ``rope_scaling`` null)
      o_h = softmax(([q_nope, q_rope]_h . [k_nope_h, k_rope])
                    / sqrt(Dn + Dr), key j <= query i) v_h
      h' = x + o Wo;  m = n2(h')
      l < ``first_k_dense_replace``: y = h' + (silu(m Wg_f) * (m Wu_f)) Wd_f
      else: s = sigmoid(m Wr) over ALL ``n_routed_experts``; the
          ``num_experts_per_tok`` largest of s + b_l (``n_group`` 1: no
          group limit); w_e = ``routed_scaling_factor`` * s_e / (sum of
          the chosen s + 1e-20)   (the bias chooses, it does not weigh)
          y = h' + shared(m) + sum over the chosen e in ``local_experts``
              of w_e * (silu(m Wg_e) * (m Wu_e)) Wd_e
          shared: one gated FFN of ``n_shared_experts`` *
          ``moe_intermediate_size``
    logits = norm(x) Whead          (the vocabulary rows held here)
    loss = mean next-token cross-entropy; no auxiliary loss

The share: ``query_heads`` is the contiguous range of heads held here,
so ``Wq`` and ``Wkv_b`` have those heads' columns only, ``Wo`` their
rows, and ``o Wo`` is their part of the sum over heads; ``Wkv_a`` and
``n_kv`` are whole (every rank computes the latent and the rotary key
alike); the router keeps its published width and its top-k of all
experts, only the experts in ``local_experts`` are computed; nothing
stands in for what other ranks would add.

``operand_dtype`` is the control of ``correct``: the same mathematics
with every operand of a matmul that the configuration runs in its
compute dtype rounded to that type first (an 8-bit float with a scale
per tensor). The router product is float32 in the configuration and
stays so in the control. ``None`` is the reference itself.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# what the families' references share: the norm, the gated FFN
from benchmark.references.laguna import _gated, _rms_norm
# the rounded-operand matmul of the control is the families' common one
from benchmark.references.transformer import HIGHEST, _mm, _rounded

INIT_STD = 0.02     # assumed: initializer_range
QUERY_BLOCK = 1024  # queries whose scores against all keys are held at once
BIAS = "e_score_correction_bias"


def _heads(cfg: dict) -> int:
    return cfg["query_heads"][1] - cfg["query_heads"][0]


def _sparse(cfg: dict, layer: int) -> bool:
    return layer >= cfg["first_k_dense_replace"]


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    w, wd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    ws = w * cfg["n_shared_experts"]
    lo, hi = cfg["local_experts"]
    heads = _heads(cfg)
    shapes = {"embed/embedding": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"block{i}/"
        shapes.update({
            b + "n1/scale": (d,), b + "q/kernel": (d, heads * (dn + dr)),
            b + "kv_a/kernel": (d, r + dr), b + "kv_norm/scale": (r,),
            b + "kv_b/kernel": (r, heads * (dn + dv)),
            b + "o/kernel": (heads * dv, d), b + "n2/scale": (d,)})
        if not _sparse(cfg, i):
            shapes.update({
                b + "ffn_gate/kernel": (d, wd), b + "ffn_up/kernel": (d, wd),
                b + "ffn_down/kernel": (wd, d)})
        else:
            shapes.update({
                b + "router/kernel": (d, cfg["n_routed_experts"]),
                b + "shared_gate/kernel": (d, ws),
                b + "shared_up/kernel": (d, ws),
                b + "shared_down/kernel": (ws, d),
                b + "w_gate": (hi - lo, d, w), b + "w_up": (hi - lo, d, w),
                b + "w_down": (hi - lo, w, d)})
    shapes.update({"norm/scale": (d,), "head/kernel": (d, v)})
    return shapes


def num_params(cfg: dict) -> int:
    return sum(math.prod(shape) for shape in param_shapes(cfg).values())


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Every TRAINED weight from the seed in ONE jitted call on the
    default device, float32: matrices, expert stacks and the embedding
    normal(0, 0.02), RMSNorm scales 1. The correction bias is no weight:
    :func:`correction_bias`."""
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


def correction_bias(cfg: dict) -> Dict[str, np.ndarray]:
    """``{block<l>/e_score_correction_bias: [n_routed_experts] float32}``
    for the sparse layers: a constant of the configuration (its group
    ``e_score_correction_bias``: a seed and a standard deviation), not
    of the run's seed and not trained. The published rule that moves it
    (``noaux_tc``) acts outside the loss and publishes no speed; a
    checkpoint carries the values it ended at, and these stand for them:
    normal(0, std), large enough beside the sigmoid scores' spread that
    a share of the tokens choose another top-k than the scores alone
    would."""
    spec = cfg[BIAS]
    return {f"block{i}/{BIAS}": np.random.default_rng(
        [spec["seed"], i]).normal(0.0, spec["std"],
                                  cfg["n_routed_experts"]).astype(np.float32)
        for i in range(cfg["num_hidden_layers"]) if _sparse(cfg, i)}


def _turned(x, t: int, theta: float):
    """Rotary positions on all dims of ``x`` [T, ..., Dr], neighbours
    paired: (x_2i + i x_2i+1) exp(i p theta^(-2i/Dr)), written back as
    (real parts, then imaginary parts): the order of a head's dims is
    the same in queries and keys, and their product does not see it."""
    dr = x.shape[-1]
    freq = jnp.asarray([theta ** (-2.0 * i / dr) for i in range(dr // 2)],
                       jnp.float32)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None] * freq).reshape(
        (t,) + (1,) * (x.ndim - 2) + (dr // 2,))
    re, im, cos, sin = x[..., 0::2], x[..., 1::2], jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([re * cos - im * sin, re * sin + im * cos], -1)


def attention(q, k, v, od=None):
    """``q``, ``k`` [T, heads, Dqk] on ``v`` [T, heads, Dv], positions
    already on them: softmax(q k^T / sqrt(Dqk)) v under the causal mask.
    The [T, T] product is taken ``QUERY_BLOCK`` queries at a time
    against every key (T padded to whole blocks with queries nobody
    reads), a block computed again on the way back. Returns
    [T, heads * Dv]."""
    t, heads, dqk = q.shape
    if od is not None:      # a scale per tensor, not per block
        q, k, v = (_rounded(x, od) for x in (q, k, v))
    block = min(QUERY_BLOCK, t)
    nb = -(-t // block)
    q = jnp.pad(q, ((0, nb * block - t), (0, 0), (0, 0)))

    @jax.checkpoint
    def some_queries(q, pos, k, v):
        mask = pos[:, None] >= jnp.arange(t)[None]
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(dqk))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        if od is not None:
            p = _rounded(p, od)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    o = jax.lax.map(lambda rows: some_queries(*rows, k, v), (
        q.reshape(nb, block, heads, dqk),
        jnp.arange(nb * block).reshape(nb, block)))
    return o.reshape(nb * block, heads * v.shape[-1])[:t]


def router_weights(m, kernel, bias, k: int, scale: float):
    """(chosen experts [T, k], their weights): sigmoid scores over ALL
    experts in float32 at ``highest``; the ``k`` largest of score +
    bias; the chosen SCORES, normalised, times ``scale``."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", m, kernel, precision=HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias, k)
    top = jnp.take_along_axis(scores, chosen, -1)
    return chosen, scale * top / (top.sum(-1, keepdims=True) + 1e-20)


def attention_branch(params, b: str, x, cfg: dict, od=None):
    """``o Wo`` of the held heads for one sequence, ``x`` [T, D]."""
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    t, heads, theta = x.shape[0], _heads(cfg), float(cfg["rope_theta"])
    a = _rms_norm(x, params[b + "n1/scale"], cfg["rms_norm_eps"])
    q = _mm("td,de->te", a, params[b + "q/kernel"], od).reshape(
        t, heads, dn + dr)
    latent = _mm("td,de->te", a, params[b + "kv_a/kernel"], od)
    c = _rms_norm(latent[:, :r], params[b + "kv_norm/scale"],
                  cfg["rms_norm_eps"])
    kv = _mm("tr,re->te", c, params[b + "kv_b/kernel"], od).reshape(
        t, heads, dn + dv)
    k_rope = jnp.broadcast_to(
        _turned(latent[:, None, r:], t, theta), (t, heads, dr))
    o = attention(
        jnp.concatenate([q[..., :dn], _turned(q[..., dn:], t, theta)], -1),
        jnp.concatenate([kv[..., :dn], k_rope], -1), kv[..., dn:], od)
    return _mm("te,ed->td", o, params[b + "o/kernel"], od)


def layer(params, b: str, x, sparse: bool, bias, cfg: dict, od=None):
    """A layer (parameters under the prefix ``b``; ``bias`` the sparse
    layer's correction bias) on one sequence, ``x`` [T, D]."""
    x = x + attention_branch(params, b, x, cfg, od)
    m = _rms_norm(x, params[b + "n2/scale"], cfg["rms_norm_eps"])
    if not sparse:
        return x + _gated(m, params[b + "ffn_gate/kernel"],
                          params[b + "ffn_up/kernel"],
                          params[b + "ffn_down/kernel"], od)
    chosen, weights = router_weights(
        m, params[b + "router/kernel"], bias, cfg["num_experts_per_tok"],
        cfg["routed_scaling_factor"])
    y = _gated(m, params[b + "shared_gate/kernel"],
               params[b + "shared_up/kernel"],
               params[b + "shared_down/kernel"], od)
    lo, hi = cfg["local_experts"]

    def add_expert(y, held):
        e, gate, up, down = held
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        return y + weight[:, None] * _gated(m, gate, up, down, od), None

    # a loop over the experts held here, one at a time (a scan: the
    # experts' code is compiled once, not once an expert)
    y, _ = jax.lax.scan(add_expert, y, (
        jnp.arange(lo, hi), params[b + "w_gate"], params[b + "w_up"],
        params[b + "w_down"]))
    return x + y


def sequence_loss(params: Dict[str, jax.Array], toks, cfg: dict,
                  operand_dtype: Optional[str] = None):
    """The loss of ONE sequence, ``toks`` [T+1] int32. A layer's
    intermediates are computed again on the way back (``jax.checkpoint``
    around each layer: the same arithmetic, twice). The sparse layers,
    which are alike, run as one loop over their stacked weights and
    biases (a scan: their code is compiled once, which keeps the program
    inside the chip machine's compile cache)."""
    od = None if operand_dtype is None else jnp.dtype(operand_dtype)
    tokens, nxt = toks[:-1], toks[1:]
    x = params["embed/embedding"][tokens]
    biases = correction_bias(cfg)

    def of_layer(l):
        return {n[len(f"block{l}/"):]: p for n, p in params.items()
                if n.startswith(f"block{l}/")}

    depth, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    for l in range(min(dense, depth)):
        x = jax.checkpoint(lambda mine, x: layer(
            mine, "", x, False, None, cfg, od))(of_layer(l), x)
    if depth > dense:
        one = jax.checkpoint(lambda mine, bias, x: layer(
            mine, "", x, True, bias, cfg, od))
        x, _ = jax.lax.scan(
            lambda x, xs: (one(*xs, x), None), x,
            (jax.tree_util.tree_map(
                lambda *a: jnp.stack(a),
                *(of_layer(l) for l in range(dense, depth))),
             jnp.stack([biases[f"block{l}/{BIAS}"]
                        for l in range(dense, depth)])))
    x = _rms_norm(x, params["norm/scale"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(
        _mm("td,dv->tv", x, params["head/kernel"], od), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, nxt[:, None], axis=-1))


def loss_and_grads(params, toks, cfg: dict,
                   operand_dtype: Optional[str] = None):
    """``toks`` [B, T+1]: the mean over the sequences of each one's
    loss and gradient, a sequence at a time (every sequence has the
    same number of tokens, so this is the batch's mean)."""
    grad = jax.value_and_grad(sequence_loss)

    def add(total, seq):
        return jax.tree_util.tree_map(
            jnp.add, total, grad(params, seq, cfg, operand_dtype)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, params))
    total, _ = jax.lax.scan(add, zero, toks)
    return jax.tree_util.tree_map(lambda s: s / toks.shape[0], total)


def live_score_entries(cfg: dict, seq_len: int) -> int:
    """Score entries the causal mask keeps, one sequence, all layers
    and held heads: T(T+1)/2 a head."""
    return (cfg["num_hidden_layers"] * _heads(cfg)
            * seq_len * (seq_len + 1) // 2)


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward pass of THIS SHARE requires per token at
    sequence length T (multiply-add = 2):
        per layer   q 2*D*H*(Dn+Dr); kv_a 2*D*(r+Dr), whole;
                    kv_b 2*r*H*(Dn+Dv); o 2*H*Dv*D;
                    QK^T over the LIVE score entries only, 2*(Dn+Dr)
                    an entry, and PV, 2*Dv an entry (a head's mean
                    context is (T+1)/2)
        dense layer 3 * 2*D*W_dense
        sparse      router 2*D*E; shared expert 3 * 2*D*W_shared; the
                    rows a token sends to the experts held here, at
                    their EXPECTED number under even routing,
                    k * E_local / E, each 3 * 2*D*W_expert
        head        2*D*V over the vocabulary rows held here
    Under skewed routing the rows routed here differ from the
    expectation: ``kanana.local_row_share`` reports them, and the count
    stays what even routing requires. Masked score entries, lookups,
    norms, rotary positions, sigmoids, softmax, SiLU and the combine
    are not counted, nor anything computed a second time on the way
    back."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    w, heads = cfg["moe_intermediate_size"], _heads(cfg)
    lo, hi = cfg["local_experts"]
    rows = cfg["num_experts_per_tok"] * (hi - lo) / cfg["n_routed_experts"]
    depth = cfg["num_hidden_layers"]
    sparse = sum(_sparse(cfg, i) for i in range(depth))
    attention_proj = 2 * (d * heads * (dn + dr) + d * (r + dr)
                          + r * heads * (dn + dv) + heads * dv * d)
    return (depth * attention_proj
            + 2.0 * (dn + dr + dv) * live_score_entries(cfg, seq_len)
            / seq_len
            + (depth - sparse) * 6 * d * cfg["intermediate_size"]
            + sparse * (2 * d * cfg["n_routed_experts"]
                        + 6 * d * w * cfg["n_shared_experts"]
                        + rows * 6 * d * w)
            + 2 * d * v)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (twice forward); nothing recomputed counts."""
    return 3 * forward_flops_per_token(cfg, seq_len)
