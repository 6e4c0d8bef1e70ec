"""Plain reference for the ``laguna`` family: one rank's share of a
Laguna decoder (poolside/Laguna-XS.2) in straightforward ``jax.numpy``,
float32, matmuls at ``highest``.

No flax, no sort, no grouped matmul, no blocking, nothing taken from
the program: the attention is the ``[T, T]`` product per held query
head with the mask written out, the experts are a loop over the experts
held here with a mask, YaRN's frequencies are computed here from the
formula. The weights are made here from the seed (:func:`init_params`)
under the path names the program's parameter tree happens to use, and
handed to both sides.

Equations (``n*`` RMSNorm with a learned scale, eps ``rms_norm_eps``;
hd = ``head_dim``; the published model has H_l query heads in layer l,
``num_attention_heads_per_layer``, over 8 key/value heads, query head h
reading key/value head h // (H_l / 8)):
    x = wte[tokens]
    layer l of kind ``layer_types[l]``:
      a = n1(x); q = a Wq [heads x hd]; k, v = a Wk, a Wv [kv x hd];
      g = sigmoid(a Wg) [heads x hd]
      full_attention:    rotary positions on the first
          ``partial_rotary_factor`` * hd dims of q and k (half-split
          layout); frequencies by YaRN (HF ``_compute_yarn_parameters``):
          f_i = theta^(-2i/dim); c(r) = dim * ln(original / (2 pi r)) /
          (2 ln theta); low = floor(c(beta_fast)), high = ceil(c(beta_slow));
          ramp_i = clip((i - low) / (high - low), 0, 1);
          f'_i = f_i / factor * ramp_i + f_i * (1 - ramp_i);
          cos and sin times ``attention_factor``; key j <= query i
      sliding_attention: rotary positions on all hd dims, f_i =
          theta^(-2i/hd); key j with 0 <= i - j < ``sliding_window``
      o_h = softmax(q_h k_{kv(h)}^T / sqrt(hd)) v_{kv(h)}
      h' = x + (o * g) Wo;  m = n2(h')
      ``mlp_layer_types[l]`` dense:  y = h' + (silu(m Wg_f) * (m Wu_f)) Wd_f
      sparse: s = sigmoid(m Wr) over ALL ``num_experts``; the
          ``num_experts_per_tok`` largest; w_e = ``moe_routed_scaling_factor``
          * s_e / (sum of the chosen s)
          y = h' + shared(m) + sum over the chosen e in ``local_experts``
              of w_e * (silu(m Wg_e) * (m Wu_e)) Wd_e
    logits = norm(x) Whead          (the vocabulary rows held here)
    loss = mean next-token cross-entropy; no auxiliary loss

The share: the configuration's ``query_heads`` (per layer) and
``key_value_heads`` are the contiguous head ranges held here, so the
weights have those heads' columns only and ``(o * g) Wo`` is their part
of the sum over heads; the router keeps its published width and its
top-k of all experts, only the experts in ``local_experts`` are
computed; nothing stands in for what other ranks would add.

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

# the rounded-operand matmul of the control is the families' common one
from benchmark.references.transformer import HIGHEST, _mm

INIT_STD = 0.02     # assumed: initializer_range


def _heads(cfg: dict, layer: int) -> Tuple[int, int]:
    """(query heads, key/value heads) held here in ``layer``."""
    q, kv = cfg["query_heads"][layer], cfg["key_value_heads"]
    return q[1] - q[0], kv[1] - kv[0]


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    w, wd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    ws = cfg["shared_expert_intermediate_size"]
    lo, hi = cfg["local_experts"]
    shapes = {"embed/embedding": (v, d)}
    for i, mlp in enumerate(cfg["mlp_layer_types"]):
        b = f"block{i}/"
        heads, kv = _heads(cfg, i)
        shapes.update({
            b + "n1/scale": (d,),
            b + "q/kernel": (d, heads * hd), b + "k/kernel": (d, kv * hd),
            b + "v/kernel": (d, kv * hd), b + "gate/kernel": (d, heads * hd),
            b + "o/kernel": (heads * hd, d), b + "n2/scale": (d,)})
        if mlp == "dense":
            shapes.update({
                b + "ffn_gate/kernel": (d, wd), b + "ffn_up/kernel": (d, wd),
                b + "ffn_down/kernel": (wd, d)})
        else:
            shapes.update({
                b + "router/kernel": (d, cfg["num_experts"]),
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


def rotary_table(rope: dict, head_dim: int, t: int):
    """(cos, sin) [T, rotated dims] of one ``rope_parameters`` block,
    the attention factor already on them."""
    dim = int(head_dim * rope["partial_rotary_factor"])
    theta = float(rope["rope_theta"])
    inv = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    scale = 1.0
    if rope["rope_type"] == "yarn":
        def turning(rotations):
            return dim * math.log(
                rope["original_max_position_embeddings"]
                / (2 * math.pi * rotations)) / (2 * math.log(theta))

        low = max(math.floor(turning(rope["beta_fast"])), 0)
        high = min(math.ceil(turning(rope["beta_slow"])), dim - 1)
        if high == low:
            high += 0.001       # HF's guard
        for i, f in enumerate(inv):
            ramp = min(max((i - low) / (high - low), 0.0), 1.0)
            inv[i] = f / rope["factor"] * ramp + f * (1.0 - ramp)
        scale = rope["attention_factor"]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rope(x, cos, sin):
    """[T, heads, head_dim]; the leading dims of a head, as many as the
    table has, are rotated (their two halves are the pairs' members),
    the others pass."""
    rot = cos.shape[-1]
    a, rest = x[..., :rot], x[..., rot:]
    turned = jnp.concatenate([-a[..., rot // 2:], a[..., :rot // 2]], -1)
    return jnp.concatenate(
        [a * cos[:, None] + turned * sin[:, None], rest], -1)


def _gated(m, gate, up, down, od):
    return _mm("tw,wd->td",
               jax.nn.silu(_mm("td,dw->tw", m, gate, od))
               * _mm("td,dw->tw", m, up, od), down, od)


def layer(params, b: str, x, i: int, cfg: dict, od=None):
    """Layer ``i`` (parameters under the prefix ``b``) on one sequence,
    ``x`` [T, D]."""
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    t = x.shape[0]
    heads, kv = _heads(cfg, i)
    kind = cfg["layer_types"][i]
    pos = jnp.arange(t)
    behind = pos[:, None] - pos[None]       # i - j
    mask = behind >= 0
    if kind == "sliding_attention":
        mask = mask & (behind < cfg["sliding_window"])
    cos, sin = rotary_table(cfg["rope_parameters"][kind], hd, t)
    a = _rms_norm(x, params[b + "n1/scale"], eps)
    q = _mm("td,de->te", a, params[b + "q/kernel"], od).reshape(t, heads, hd)
    k = _mm("td,de->te", a, params[b + "k/kernel"], od).reshape(t, kv, hd)
    v = _mm("td,de->te", a, params[b + "v/kernel"], od).reshape(t, kv, hd)
    g = jax.nn.sigmoid(_mm("td,de->te", a, params[b + "gate/kernel"], od))
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    # held query head h reads the held key/value head h // (heads / kv)
    of = jnp.arange(heads) // (heads // kv)
    s = _mm("qhd,khd->hqk", q, k[:, of], od) / jnp.sqrt(jnp.float32(hd))
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", p, v[:, of], od).reshape(t, heads * hd)
    x = x + _mm("te,ed->td", o * g, params[b + "o/kernel"], od)
    m = _rms_norm(x, params[b + "n2/scale"], eps)
    if cfg["mlp_layer_types"][i] == "dense":
        return x + _gated(m, params[b + "ffn_gate/kernel"],
                          params[b + "ffn_up/kernel"],
                          params[b + "ffn_down/kernel"], od)
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", m, params[b + "router/kernel"], precision=HIGHEST))
    top, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    weights = cfg["moe_routed_scaling_factor"] * top / top.sum(
        -1, keepdims=True)
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


def _alike(cfg: dict, i: int, j: int) -> bool:
    """Layers ``i`` and ``j`` run the same code on weights of the same
    shapes."""
    return all(cfg[key][i] == cfg[key][j] for key in
               ("layer_types", "mlp_layer_types", "query_heads"))


def sequence_loss(params: Dict[str, jax.Array], toks, cfg: dict,
                  operand_dtype: Optional[str] = None):
    """The loss of ONE sequence, ``toks`` [T+1] int32. A layer's
    intermediates are computed again on the way back (``jax.checkpoint``
    around each layer: the same arithmetic, twice), so that five layers
    of float32 ``[heads, T, T]`` scores fit the chip beside the weights.
    Layers that follow each other and are alike run as one loop over
    their stacked weights (a scan: their code is compiled once, which
    keeps the program inside the chip machine's compile cache)."""
    od = None if operand_dtype is None else jnp.dtype(operand_dtype)
    tokens, nxt = toks[:-1], toks[1:]
    x = params["embed/embedding"][tokens]
    depth, i = cfg["num_hidden_layers"], 0
    while i < depth:
        j = i + 1
        while j < depth and _alike(cfg, i, j):
            j += 1
        run = [{n[len(f"block{l}/"):]: p for n, p in params.items()
                if n.startswith(f"block{l}/")} for l in range(i, j)]
        one = jax.checkpoint(
            lambda mine, x, i=i: layer(mine, "", x, i, cfg, od))
        x, _ = jax.lax.scan(
            lambda x, mine: (one(mine, x), None), x,
            jax.tree_util.tree_map(lambda *a: jnp.stack(a), *run))
        i = j
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
    """Score entries the masks keep, one sequence, all layers and held
    query heads: T(T+1)/2 a full head; a sliding head sum_i min(i+1, W)."""
    t, w = seq_len, min(cfg["sliding_window"], seq_len)
    per_kind = {"full_attention": t * (t + 1) // 2,
                "sliding_attention": w * (w + 1) // 2
                + (t - w) * cfg["sliding_window"]}
    return sum(per_kind[kind] * _heads(cfg, i)[0]
               for i, kind in enumerate(cfg["layer_types"]))


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward pass of THIS SHARE requires per token at
    sequence length T (multiply-add = 2):
        per layer   q, gate, o over the held query heads 3 * 2*D*H*hd;
                    k, v over the held key/value heads 2 * 2*D*KV*hd;
                    QK^T and PV over the LIVE score entries only,
                    4*hd an entry (a full head's mean context is
                    (T+1)/2, a sliding head's at most the window)
        dense layer 3 * 2*D*W_dense
        sparse      router 2*D*E; shared expert 3 * 2*D*W_shared; the
                    rows a token sends to the experts held here, at
                    their EXPECTED number under even routing,
                    k * E_local / E, each 3 * 2*D*W_expert
        head        2*D*V over the vocabulary rows held here
    Under skewed routing the rows routed here differ from the
    expectation: ``laguna.local_row_share`` reports them, and the count
    stays what even routing requires. Masked score entries, lookups,
    norms, rotary positions, sigmoids, softmax, SiLU, the gate's product
    and the combine are not counted."""
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    lo, hi = cfg["local_experts"]
    rows = cfg["num_experts_per_tok"] * (hi - lo) / cfg["num_experts"]
    total = 4.0 * hd * live_score_entries(cfg, seq_len) / seq_len + 2 * d * v
    for i, mlp in enumerate(cfg["mlp_layer_types"]):
        heads, kv = _heads(cfg, i)
        total += 2 * d * hd * (3 * heads + 2 * kv)
        if mlp == "dense":
            total += 6 * d * cfg["intermediate_size"]
        else:
            total += (2 * d * cfg["num_experts"]
                      + 6 * d * cfg["shared_expert_intermediate_size"]
                      + rows * 6 * d * cfg["moe_intermediate_size"])
    return total


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (twice forward); nothing recomputed counts."""
    return 3 * forward_flops_per_token(cfg, seq_len)
