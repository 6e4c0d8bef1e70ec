"""Plain reference for the ``mellum`` family: one rank's share of a
Mellum 2 decoder (JetBrains/Mellum2-12B-A2.5B) in straightforward
``jax.numpy``, float32, matmuls at ``highest``.

No flax, no sort, no grouped matmul, no window blocking, no kernel,
nothing taken from the program: the attention is the ``[T, T]`` product
per held query head with the mask written out (computed a block of
``QUERY_BLOCK`` queries against ALL keys at a time, so that 8,192
positions fit), the experts are a loop over the experts held here with a
mask, YaRN's frequencies come from the formula (the table the
references share, ``references/laguna.rotary_table``, over the whole
head). The weights
are made here from the seed (:func:`init_params`) under the path names
the program's parameter tree happens to use, and handed to both sides.

Equations (``n*`` RMSNorm with a learned scale, eps ``rms_norm_eps``;
hd = ``head_dim``; the published model has 32 query heads over 4
key/value heads, query head h reading key/value head h // 8):
    x = wte[tokens]
    layer l of kind ``layer_types[l]``:
      a = n1(x); q = a Wq [heads x hd]; k, v = a Wk, a Wv [kv x hd]
      sliding_attention: rotary positions on all hd dims of q and k
          (half-split layout), f_i = theta^(-2i/hd); key j with
          0 <= i - j < ``sliding_window``
      full_attention:    rotary positions on all hd dims; frequencies by
          YaRN (HF ``_compute_yarn_parameters``): c(r) = hd * ln(original
          / (2 pi r)) / (2 ln theta); low = floor(c(beta_fast)), high =
          ceil(c(beta_slow)); ramp_i = clip((i - low) / (high - low), 0,
          1); f'_i = f_i / factor * ramp_i + f_i * (1 - ramp_i); cos and
          sin times ``attention_factor``; key j <= query i
      o_h = softmax(q_h k_{kv(h)}^T / sqrt(hd)) v_{kv(h)}
      h' = x + o Wo;  m = n2(h')
      p = softmax(m Wr) over ALL ``num_experts``; the
          ``num_experts_per_tok`` largest; w_e = p_e / (sum of the
          chosen p)   (``norm_topk_prob``)
      y = h' + sum over the chosen e in ``local_experts`` of
          w_e * (silu(m Wg_e) * (m Wu_e)) Wd_e
    logits = norm(x) Whead          (the vocabulary rows held here)
    loss = mean next-token cross-entropy; no auxiliary loss

The share: the configuration's ``query_heads`` and ``key_value_heads``
are the contiguous head ranges held here, so the weights have those
heads' columns only and ``o Wo`` is their part of the sum over heads;
the router keeps its published width and its top-k of all experts, only
the experts in ``local_experts`` are computed; nothing stands in for
what other ranks would add.

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

# what the families' references share: the norm, the gated FFN, rotary
# positions from the formula (YaRN as HF ``_compute_yarn_parameters``)
from benchmark.references.laguna import _gated, _rms_norm, _rope
from benchmark.references.laguna import rotary_table as _rotary_table
# the rounded-operand matmul of the control is the families' common one
from benchmark.references.transformer import HIGHEST, _mm, _rounded

INIT_STD = 0.02     # assumed: initializer_range
QUERY_BLOCK = 1024  # queries whose scores against all keys are held at once


def _heads(cfg: dict) -> Tuple[int, int]:
    """(query heads, key/value heads) held here."""
    q, kv = cfg["query_heads"], cfg["key_value_heads"]
    return q[1] - q[0], kv[1] - kv[0]


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    w = cfg["moe_intermediate_size"]
    lo, hi = cfg["local_experts"]
    heads, kv = _heads(cfg)
    shapes = {"embed/embedding": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"block{i}/"
        shapes.update({
            b + "n1/scale": (d,),
            b + "q/kernel": (d, heads * hd), b + "k/kernel": (d, kv * hd),
            b + "v/kernel": (d, kv * hd), b + "o/kernel": (heads * hd, d),
            b + "n2/scale": (d,),
            b + "router/kernel": (d, cfg["num_experts"]),
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


def rotary_table(rope: dict, head_dim: int, t: int):
    """(cos, sin) [T, head_dim] of one ``rope_parameters`` block, the
    attention factor already on them. Neither block names a
    ``partial_rotary_factor``: every dim of a head turns."""
    return _rotary_table(dict(rope, partial_rotary_factor=1), head_dim, t)


def attention(q, k, v, window: Optional[int], od=None):
    """``q`` [T, heads, hd] on ``k``, ``v`` [T, kv, hd], positions
    already on them: softmax(q k^T / sqrt(hd)) v under the mask (key j
    <= query i, and with ``window`` also i - j < window). The [T, T]
    product is taken ``QUERY_BLOCK`` queries at a time against every key
    (T padded to whole blocks with queries nobody reads), a block
    computed again on the way back. Returns [T, heads * hd]."""
    t, heads, hd = q.shape
    # held query head h reads the held key/value head h // (heads / kv)
    of = jnp.arange(heads) // (heads // k.shape[1])
    if od is not None:      # a scale per tensor, not per block
        q, k, v = (_rounded(x, od) for x in (q, k, v))
    k, v = k[:, of], v[:, of]
    block = min(QUERY_BLOCK, t)
    nb = -(-t // block)
    q = jnp.pad(q, ((0, nb * block - t), (0, 0), (0, 0)))

    @jax.checkpoint
    def some_queries(q, pos, k, v):
        behind = pos[:, None] - jnp.arange(t)[None]         # i - j
        mask = behind >= 0
        if window is not None:
            mask = mask & (behind < window)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        if od is not None:
            p = _rounded(p, od)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    o = jax.lax.map(lambda rows: some_queries(*rows, k, v), (
        q.reshape(nb, block, heads, hd),
        jnp.arange(nb * block).reshape(nb, block)))
    return o.reshape(nb * block, heads * hd)[:t]


def router_weights(m, kernel, k: int):
    """(chosen experts [T, k], their weights): softmax over ALL experts
    in float32 at ``highest``, the ``k`` largest, normalised to sum 1."""
    probs = jax.nn.softmax(jnp.einsum(
        "td,de->te", m, kernel, precision=HIGHEST), axis=-1)
    top, chosen = jax.lax.top_k(probs, k)
    return chosen, top / top.sum(-1, keepdims=True)


def layer(params, b: str, x, kind: str, cfg: dict, od=None):
    """A layer of ``kind`` (parameters under the prefix ``b``) on one
    sequence, ``x`` [T, D]."""
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    t = x.shape[0]
    heads, kv = _heads(cfg)
    cos, sin = rotary_table(cfg["rope_parameters"][kind], hd, t)
    a = _rms_norm(x, params[b + "n1/scale"], eps)
    q = _mm("td,de->te", a, params[b + "q/kernel"], od).reshape(t, heads, hd)
    k = _mm("td,de->te", a, params[b + "k/kernel"], od).reshape(t, kv, hd)
    v = _mm("td,de->te", a, params[b + "v/kernel"], od).reshape(t, kv, hd)
    o = attention(_rope(q, cos, sin), _rope(k, cos, sin), v,
                  cfg["sliding_window"] if kind == "sliding_attention"
                  else None, od)
    x = x + _mm("te,ed->td", o, params[b + "o/kernel"], od)
    m = _rms_norm(x, params[b + "n2/scale"], eps)
    chosen, weights = router_weights(m, params[b + "router/kernel"],
                                     cfg["num_experts_per_tok"])
    lo, hi = cfg["local_experts"]

    def add_expert(y, held):
        e, gate, up, down = held
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        return y + weight[:, None] * _gated(m, gate, up, down, od), None

    # a loop over the experts held here, one at a time (a scan: the
    # experts' code is compiled once, not once an expert)
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(lo, hi), params[b + "w_gate"], params[b + "w_up"],
        params[b + "w_down"]))
    return x + y


def sequence_loss(params: Dict[str, jax.Array], toks, cfg: dict,
                  operand_dtype: Optional[str] = None):
    """The loss of ONE sequence, ``toks`` [T+1] int32. A layer's
    intermediates are computed again on the way back (``jax.checkpoint``
    around each layer: the same arithmetic, twice). Layers of one kind
    that follow each other run as one loop over their stacked weights (a
    scan: their code is compiled once, which keeps the program inside
    the chip machine's compile cache)."""
    od = None if operand_dtype is None else jnp.dtype(operand_dtype)
    tokens, nxt = toks[:-1], toks[1:]
    x = params["embed/embedding"][tokens]
    kinds, i = cfg["layer_types"], 0
    while i < len(kinds):
        j = i + 1
        while j < len(kinds) and kinds[j] == kinds[i]:
            j += 1
        run = [{n[len(f"block{l}/"):]: p for n, p in params.items()
                if n.startswith(f"block{l}/")} for l in range(i, j)]
        one = jax.checkpoint(
            lambda mine, x, kind=kinds[i]: layer(mine, "", x, kind, cfg, od))
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
    return _heads(cfg)[0] * sum(per_kind[kind]
                                for kind in cfg["layer_types"])


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward pass of THIS SHARE requires per token at
    sequence length T (multiply-add = 2):
        per layer   q, o over the held query heads 2 * 2*D*H*hd;
                    k, v over the held key/value heads 2 * 2*D*KV*hd;
                    QK^T and PV over the LIVE score entries only,
                    4*hd an entry (a full head's mean context is
                    (T+1)/2, a sliding head's at most the window);
                    router 2*D*E; the rows a token sends to the experts
                    held here, at their EXPECTED number under even
                    routing, k * E_local / E, each 3 * 2*D*W_expert
        head        2*D*V over the vocabulary rows held here
    Under skewed routing the rows routed here differ from the
    expectation: ``mellum.local_row_share`` reports them, and the count
    stays what even routing requires. Masked score entries, lookups,
    norms, rotary positions, softmax, SiLU and the combine are not
    counted, nor anything computed a second time on the way back."""
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    lo, hi = cfg["local_experts"]
    heads, kv = _heads(cfg)
    rows = cfg["num_experts_per_tok"] * (hi - lo) / cfg["num_experts"]
    a_layer = (2 * d * hd * (2 * heads + 2 * kv) + 2 * d * cfg["num_experts"]
               + rows * 6 * d * cfg["moe_intermediate_size"])
    return (cfg["num_hidden_layers"] * a_layer + 2 * d * v
            + 4.0 * hd * live_score_entries(cfg, seq_len) / seq_len)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (twice forward); nothing recomputed counts."""
    return 3 * forward_flops_per_token(cfg, seq_len)
