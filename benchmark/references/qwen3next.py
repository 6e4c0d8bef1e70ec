"""Plain reference for the ``qwen3next`` family: one rank's share of a
Qwen3-Next decoder (Qwen/Qwen3-Next-80B-A3B) in straightforward
``jax.numpy``, float32, matmuls at ``highest``.

No flax, no sort, no grouped matmul, no chunked scan, nothing taken
from the program: a linear-attention layer is the TOKEN recurrence
written out, one token after another; the attention is the ``[T, T]``
product per held query head with the mask written out; the experts are
a loop over the experts held here with a mask. The weights are made
here from the seed (:func:`init_params`) under the path names the
program's parameter tree happens to use, and handed to both sides.

Equations (``n*`` RMSNorm, eps ``rms_norm_eps``, in the zero-centred
form ``x / rms(x) * (1 + w)``; one sequence, ``x`` [T, D]):
    x = wte[tokens]
    layer l, ``layer_types[l]``:   x = x + mixer(n1(x)); x = x + moe(n2(x))
    linear_attention, h = n1(x); Hk key heads held, r = Hv / Hk value
    heads a key head, dk = ``linear_key_head_dim``, dv =
    ``linear_value_head_dim``:
      [q, k, v, z] = h W_qkvz  (a key head's columns together: q [dk],
          k [dk], its r value heads' v [r dv] and z [r dv])
      [b, a] = h W_ba          (a key head's r and r scalars together)
      [q, k, v] = silu(conv(concat[q, k, v])): y_t = sum_j w_j x_{t-3+j}
          a channel, ``linear_conv_kernel_dim`` 4 taps, zeros before the
          first token
      q = q / sqrt(sum q^2 + 1e-6) / sqrt(dk); k = k / sqrt(sum k^2 + 1e-6)
      beta = sigmoid(b); g = -exp(A_log) * softplus(a + dt_bias)
      value head j, state S [dk, dv] from zero, key head j // r, token
      by token:
          S = exp(g_t) S;  r = v_t - S^T k_t;  S = S + k_t (beta_t r)^T
          o_t = S^T q_t
      y = o / rms(o) * w * silu(z)  a head (plain weight);  mixer = y W_out
    full_attention, ``head_dim`` hd:
      [q, gate] = h W_q (a head's q and gate together); k, v = h W_k, h W_v
      q, k = qnorm(q), knorm(k) a head (zero-centred)
      rotary positions on the first ``partial_rotary_factor`` * hd dims
          (half-split layout), f_i = theta^(-2i/dim)
      o_h = softmax(q_h k_{kv(h)}^T / sqrt(hd)) v_{kv(h)}, key j <= query i
      mixer = (o * sigmoid(gate)) W_o
    moe, m = n2(x):
      p = softmax(m W_r) over ALL ``num_experts``; the
          ``num_experts_per_tok`` largest; w_e = p_e / (sum of the chosen p)
          (``norm_topk_prob``)
      moe = sigmoid(m w_s) * shared(m) + sum over the chosen e in
          ``local_experts`` of w_e * (silu(m Wg_e) * (m Wu_e)) Wd_e
    logits = norm(x) Whead          (the vocabulary rows held here)
    loss = mean next-token cross-entropy; no auxiliary loss

The share: ``query_heads`` / ``key_value_heads`` (full layers) and
``linear_key_heads_held`` / ``linear_value_heads_held`` (linear layers)
are the contiguous head ranges held here, so the weights have those
heads' columns only and the output projection sums their part; the
router keeps its published width and its top-k of all experts, only
the experts in ``local_experts`` are computed; nothing stands in for
what other ranks would add.

``operand_dtype`` is the control of ``correct``: the same mathematics
with every operand of a product that the configuration runs in its
compute dtype rounded to that type first (an 8-bit float with a scale
per tensor): the projections, the recurrence's three products, the
attention's two, the experts, the head. The router product is float32
in the configuration and stays so in the control. ``None`` is the
reference itself.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# the gated feed-forward and the half-split rotation are Laguna's
from benchmark.references.laguna import _gated, _rope
# the rounded-operand matmul of the control is the families' common one
from benchmark.references.transformer import HIGHEST, _mm

INIT_STD = 0.02     # assumed: initializer_range
L2_EPS = 1e-6
REMAT_TOKENS = 64   # the recurrence keeps its state this many tokens apart
LINEAR = "linear_attention"


def _held(cfg: dict) -> Tuple[int, int, int, int]:
    """(query heads, key/value heads, linear key heads, linear value
    heads) held here."""
    return tuple(cfg[key][1] - cfg[key][0] for key in (
        "query_heads", "key_value_heads", "linear_key_heads_held",
        "linear_value_heads_held"))


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    w, ws = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    heads, kv, hk, hv = _held(cfg)
    lo, hi = cfg["local_experts"]
    shapes = {"embed/embedding": (v, d)}
    for i, kind in enumerate(cfg["layer_types"]):
        b = f"block{i}/"
        shapes[b + "n1/scale"] = (d,)
        if kind == LINEAR:
            a = b + "linear_attn/"
            shapes.update({
                a + "in_proj_qkvz/kernel": (d, 2 * hk * dk + 2 * hv * dv),
                a + "in_proj_ba/kernel": (d, 2 * hv),
                a + "conv": (cfg["linear_conv_kernel_dim"],
                             2 * hk * dk + hv * dv),
                a + "A_log": (hv,), a + "dt_bias": (hv,),
                a + "norm/scale": (dv,),
                a + "out_proj/kernel": (hv * dv, d)})
        else:
            shapes.update({
                b + "q_proj/kernel": (d, 2 * heads * hd),
                b + "k_proj/kernel": (d, kv * hd),
                b + "v_proj/kernel": (d, kv * hd),
                b + "q_norm/scale": (hd,), b + "k_norm/scale": (hd,),
                b + "o_proj/kernel": (heads * hd, d)})
        shapes.update({
            b + "n2/scale": (d,),
            b + "router/kernel": (d, cfg["num_experts"]),
            b + "shared_gate/kernel": (d, ws),
            b + "shared_up/kernel": (d, ws),
            b + "shared_down/kernel": (ws, d),
            b + "shared_expert_gate/kernel": (d, 1),
            b + "w_gate": (hi - lo, d, w), b + "w_up": (hi - lo, d, w),
            b + "w_down": (hi - lo, w, d)})
    shapes.update({"norm/scale": (d,), "head/kernel": (d, v)})
    return shapes


def num_params(cfg: dict) -> int:
    return sum(math.prod(shape) for shape in param_shapes(cfg).values())


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight from the seed in ONE jitted call on the default
    device, float32: matrices, expert stacks and the embedding
    normal(0, 0.02); zero-centred RMSNorm scales 0 and the gated norm's
    plain weight 1; the convolution uniform(-1/2, 1/2) (a 4-tap filter's
    fan-in bound); ``dt_bias`` 1 and ``A_log`` = log U(0, 16), as the
    published module sets them."""
    shapes = param_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if name.endswith("linear_attn/norm/scale") or name.endswith(
                    "dt_bias"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("/scale"):
                out[name] = jnp.zeros(shape, jnp.float32)
            elif name.endswith("A_log"):
                out[name] = jnp.log(16.0 * (1.0 - jax.random.uniform(
                    k, shape, jnp.float32)))
            elif name.endswith("/conv"):
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -0.5, 0.5)
            else:
                out[name] = INIT_STD * jax.random.normal(k, shape,
                                                         jnp.float32)
        return out

    # a seed may exceed 32 signed bits: fold it in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return jax.jit(make)(key)


def _rms_norm(x, w, eps):
    """Zero-centred: the weight is the scale's distance from 1."""
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (1 + w)


def token_recurrence(q, k, v, g, beta, od=None):
    """The gated delta rule a token at a time: ``q``, ``k`` [T, H, dk],
    ``v`` [T, H, dv], ``g``, ``beta`` [T, H] -> o [T, H, dv]. A
    ``lax.scan`` over the tokens inside a scan over blocks of
    ``REMAT_TOKENS`` of them; a block's states are computed again on
    the way back (``jax.checkpoint``: the same arithmetic, twice), so
    that T / 64 + 64 states are alive and not T."""
    t, h, dk = q.shape

    def token(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = jnp.exp(g_t)[:, None, None] * s
        r = v_t - _mm("hkv,hk->hv", s, k_t, od)
        s = s + _mm("hk,hv->hkv", k_t, beta_t[:, None] * r, od)
        return s, _mm("hkv,hk->hv", s, q_t, od)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    xs = (q, k, v, g, beta)
    whole = t - t % REMAT_TOKENS
    s = jnp.zeros((h, dk, v.shape[-1]), jnp.float32)
    out = []
    if whole:
        s, o = jax.lax.scan(block, s, tuple(
            x[:whole].reshape((-1, REMAT_TOKENS) + x.shape[1:]) for x in xs))
        out.append(o.reshape((whole,) + o.shape[2:]))
    if t > whole:
        out.append(block(s, tuple(x[whole:] for x in xs))[1])
    return jnp.concatenate(out)


def linear_mixer(params, b: str, h, cfg: dict, od=None):
    """The Gated DeltaNet mixer on one sequence's normed input ``h``
    [T, D], parameters under the prefix ``b``."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    _, _, hk, hv = _held(cfg)
    r = hv // hk
    t = h.shape[0]
    qkvz = _mm("td,de->te", h, params[b + "in_proj_qkvz/kernel"],
               od).reshape(t, hk, 2 * dk + 2 * r * dv)
    ba = _mm("td,de->te", h, params[b + "in_proj_ba/kernel"],
             od).reshape(t, hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(t, hv, dv)
    write, a = ba[..., :r].reshape(t, hv), ba[..., r:].reshape(t, hv)
    mixed = jnp.concatenate([x.reshape(t, -1) for x in (q, k, v)], -1)
    taps = params[b + "conv"]
    before = jnp.concatenate(
        [jnp.zeros((taps.shape[0] - 1, mixed.shape[1]), mixed.dtype), mixed])
    mixed = jax.nn.silu(sum(
        taps[j] * before[j:j + t] for j in range(taps.shape[0])))
    q = mixed[:, :hk * dk].reshape(t, hk, dk)
    k = mixed[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = mixed[:, 2 * hk * dk:].reshape(t, hv, dv)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + L2_EPS) / math.sqrt(dk)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    g = -jnp.exp(params[b + "A_log"]) * jax.nn.softplus(
        a + params[b + "dt_bias"])
    # value head j reads key head j // r
    of = jnp.arange(hv) // r
    o = token_recurrence(q[:, of], k[:, of], v, g, jax.nn.sigmoid(write), od)
    y = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                          + cfg["rms_norm_eps"]) * params[b + "norm/scale"]
    y = (y * jax.nn.silu(z)).reshape(t, hv * dv)
    return _mm("te,ed->td", y, params[b + "out_proj/kernel"], od)


def rotary_table(cfg: dict, t: int):
    """(cos, sin) [T, rotated dims]: plain frequencies over the first
    ``partial_rotary_factor`` of a head's dims."""
    dim = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    theta = float(cfg["rope_theta"])
    inv = jnp.asarray([theta ** (-2.0 * i / dim) for i in range(dim // 2)],
                      jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def full_mixer(params, b: str, h, cfg: dict, od=None):
    """The gated softmax-attention mixer on one sequence's normed input
    ``h`` [T, D]."""
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    heads, kv, _, _ = _held(cfg)
    t = h.shape[0]
    q_gate = _mm("td,de->te", h, params[b + "q_proj/kernel"], od).reshape(
        t, heads, 2 * hd)
    q, gate = q_gate[..., :hd], q_gate[..., hd:].reshape(t, heads * hd)
    k = _mm("td,de->te", h, params[b + "k_proj/kernel"], od).reshape(
        t, kv, hd)
    v = _mm("td,de->te", h, params[b + "v_proj/kernel"], od).reshape(
        t, kv, hd)
    cos, sin = rotary_table(cfg, t)
    q = _rope(_rms_norm(q, params[b + "q_norm/scale"], eps), cos, sin)
    k = _rope(_rms_norm(k, params[b + "k_norm/scale"], eps), cos, sin)
    # held query head h reads the held key/value head h // (heads / kv)
    of = jnp.arange(heads) // (heads // kv)
    s = _mm("qhd,khd->hqk", q, k[:, of], od) / jnp.sqrt(jnp.float32(hd))
    pos = jnp.arange(t)
    p = jax.nn.softmax(
        jnp.where(pos[:, None] >= pos[None], s, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", p, v[:, of], od).reshape(t, heads * hd)
    return _mm("te,ed->td", o * jax.nn.sigmoid(gate),
               params[b + "o_proj/kernel"], od)


def moe(params, b: str, m, cfg: dict, od=None):
    """The shared expert behind its gate plus the held experts' terms,
    ``m`` [T, D] the layer's second normed input."""
    probs = jax.nn.softmax(jnp.einsum(
        "td,de->te", m, params[b + "router/kernel"], precision=HIGHEST), -1)
    top, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    weights = top / top.sum(-1, keepdims=True)
    y = jax.nn.sigmoid(_mm("td,de->te", m,
                           params[b + "shared_expert_gate/kernel"], od)) \
        * _gated(m, params[b + "shared_gate/kernel"],
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
    return y


def layer(params, b: str, x, kind: str, cfg: dict, od=None):
    """A layer of ``kind`` (parameters under the prefix ``b``) on one
    sequence, ``x`` [T, D]."""
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, params[b + "n1/scale"], eps)
    if kind == LINEAR:
        x = x + linear_mixer(params, b + "linear_attn/", h, cfg, od)
    else:
        x = x + full_mixer(params, b, h, cfg, od)
    return x + moe(params, b, _rms_norm(x, params[b + "n2/scale"], eps),
                   cfg, od)


def sequence_loss(params: Dict[str, jax.Array], toks, cfg: dict,
                  operand_dtype: Optional[str] = None):
    """The loss of ONE sequence, ``toks`` [T+1] int32. A layer's
    intermediates are computed again on the way back (``jax.checkpoint``
    around each layer). Layers that follow each other and are of one
    kind run as one loop over their stacked weights (a scan: their code
    is compiled once, which keeps the program inside the chip machine's
    compile cache)."""
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
    """Score entries the causal mask keeps, one sequence, the full
    layers' held query heads: T(T+1)/2 a head."""
    full = sum(kind != LINEAR for kind in cfg["layer_types"])
    return full * _held(cfg)[0] * seq_len * (seq_len + 1) // 2


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward pass of THIS SHARE requires per token at
    sequence length T (multiply-add = 2):
        linear layer  in_proj_qkvz 2*D*(2*Hk*dk + 2*Hv*dv), in_proj_ba
                      2*D*2*Hv, out_proj 2*Hv*dv*D; the RECURRENCE's
                      three [dk, dv] products a token and value head
                      (S^T k, k (beta r)^T, S^T q), 3 * 2*dk*dv: what
                      the mechanism needs whatever the algorithm, the
                      chunked form's extra products are not counted
        full layer    q_proj (query and gate) 2*D*2*H*hd, k and v
                      2 * 2*D*KV*hd, o_proj 2*H*hd*D; QK^T and PV over
                      the LIVE score entries only, 4*hd an entry (a
                      head's mean context is (T+1)/2)
        every layer   router 2*D*E; shared expert 3 * 2*D*W_shared; the
                      rows a token sends to the experts held here, at
                      their EXPECTED number under even routing,
                      k * E_local / E, each 3 * 2*D*W_expert
        head          2*D*V over the vocabulary rows held here
    Under skewed routing the rows routed here differ from the
    expectation: ``qwen3next.local_row_share`` reports them, and the
    count stays what even routing requires. Masked score entries,
    lookups, norms, the convolution (8 a channel), the state's decay,
    rotary positions, sigmoids, softmax, SiLU, the gates' products and
    the combine are not counted."""
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    heads, kv, hk, hv = _held(cfg)
    lo, hi = cfg["local_experts"]
    rows = cfg["num_experts_per_tok"] * (hi - lo) / cfg["num_experts"]
    total = 4.0 * hd * live_score_entries(cfg, seq_len) / seq_len + 2 * d * v
    for kind in cfg["layer_types"]:
        if kind == LINEAR:
            total += (2 * d * (2 * hk * dk + 2 * hv * dv) + 2 * d * 2 * hv
                      + 2 * hv * dv * d + hv * 6 * dk * dv)
        else:
            total += 2 * d * hd * (3 * heads + 2 * kv)
        total += (2 * d * cfg["num_experts"]
                  + 6 * d * cfg["shared_expert_intermediate_size"]
                  + rows * 6 * d * cfg["moe_intermediate_size"])
    return total


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (twice forward); nothing recomputed counts."""
    return 3 * forward_flops_per_token(cfg, seq_len)
