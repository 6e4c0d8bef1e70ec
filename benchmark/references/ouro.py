"""Plain reference for the ``ouro`` family: one rank's share of Ouro
(ByteDance/Ouro-2.6B, ``ouro``; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741), a looped language model, under the
expected-exit loss it is pre-trained with, in straightforward
``jax.numpy``, float32, matmuls at ``highest``.

No flax, no kernel, nothing taken from the program: the R passes of
the stack are a Python loop that reads the SAME parameter dict R times
(:func:`passes_loss` takes a dict a pass, so that a test can untie
them), the attention is the ``[T, T]`` product per head under the
causal mask (a slab of ``QUERY_BLOCK`` queries against all keys at a
time, so that 4,096 positions fit), the exit distribution is written
out in probabilities. The weights are made here from the seed
(:func:`init_params`) under the path names the program's parameter tree
happens to use, and handed to both sides.

Equations (``N*`` RMSNorm with a learned scale, eps ``rms_norm_eps``;
hd = ``head_dim``; R = ``total_ut_steps``; V the vocabulary rows held
here; what the config does not state is in its file under ``assumed``):
    h_0 = wte[tokens]                                   [T, D]
    a pass, t = 1..R, the same weights every pass:
      every layer (sandwich norms, four a layer):
        x = N1(h); q, k, v = x Wq, x Wk, x Wv [heads x hd], no bias
        rotary positions on all hd dims of q and k (half-split layout),
            f_i = theta^(-2i/hd), angle = position * f_i
        o_h = softmax(q_h k_h^T / sqrt(hd) over the keys j <= i) v_h
        h = h + N2(o Wo)
        x = N3(h); h = h + N4((silu(x Wg) * (x Wu)) Wd)
      h_t = Nf(h)           the normed state goes on to the next pass
      logits_t = h_t Whead;  nll_t = -log softmax(logits_t)[next token]
      g_t = sigmoid(h_t w_g + b_g)
    p_1 = g_1;  p_t = g_t prod_{j<t} (1 - g_j);  p_R = prod_{j<R} (1 - g_j)
    loss = mean over positions of
           [ sum_t p_t nll_t + beta sum_t p_t log p_t ],  beta = 0.05
           (the expected loss less beta times the entropy of p); a
           sequence, the mean over the sequences

The share: the vocabulary rows held here (embedding and head; token ids
are drawn from them and the loss is over them); the layers are whole.

``operand_dtype`` is the control of ``correct``: the same mathematics
with every operand of a matmul that the configuration runs in its
compute dtype rounded to that type first (an 8-bit float with a scale
per tensor). The gate's product is float32 in the configuration and
stays so in the control. ``None`` is the reference itself.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# what the families' references share: the norm, the gated FFN, the
# half-split rotation
from benchmark.references.laguna import _gated, _rms_norm, _rope
# rotary positions on every dim of a head, from the formula, by position id
from benchmark.references.sdar import rotary_table
# the rounded-operand matmul of the control is the families' common one
from benchmark.references.transformer import HIGHEST, _mm, _rounded

INIT_STD = 0.02     # assumed: initializer_range
QUERY_BLOCK = 1024  # queries whose scores against all keys are held at once
ENTROPY_BETA = 0.05  # assumed: the entropy term's weight (stage I)

LAYER_KEYS = ("n1/scale", "q/kernel", "k/kernel", "v/kernel", "o/kernel",
              "n2/scale", "n3/scale", "gate/kernel", "up/kernel",
              "down/kernel", "n4/scale")


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    d, v, w = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    e = cfg["num_attention_heads"] * cfg["head_dim"]
    shapes = {"embed/embedding": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"block{i}/"
        shapes.update({
            b + "n1/scale": (d,),
            b + "q/kernel": (d, e), b + "k/kernel": (d, e),
            b + "v/kernel": (d, e), b + "o/kernel": (e, d),
            b + "n2/scale": (d,), b + "n3/scale": (d,),
            b + "gate/kernel": (d, w), b + "up/kernel": (d, w),
            b + "down/kernel": (w, d), b + "n4/scale": (d,)})
    shapes.update({"norm/scale": (d,), "head/kernel": (d, v),
                   "exit_gate/kernel": (d, 1), "exit_gate/bias": (1,)})
    return shapes


def num_params(cfg: dict) -> int:
    return sum(math.prod(shape) for shape in param_shapes(cfg).values())


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight from the seed in ONE jitted call on the default
    device, float32: matrices (the gate's too) and the embedding
    normal(0, 0.02), RMSNorm scales 1, the gate's bias 0."""
    shapes = param_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if name.endswith("/scale"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("/bias"):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = INIT_STD * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    # a seed may exceed 32 signed bits: fold it in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return jax.jit(make)(key)


def attention(q, k, v, od=None):
    """``q``, ``k``, ``v`` [T, heads, hd], positions already on q and k:
    softmax(q k^T / sqrt(hd)) v over the keys j <= i. The [T, T] product
    is taken ``QUERY_BLOCK`` queries at a time against every key (padded
    to whole slabs with queries nobody reads), a slab computed again on
    the way back. Returns [T, heads * hd]."""
    t, heads, hd = q.shape
    if od is not None:      # a scale per tensor, not per slab
        q, k, v = (_rounded(x, od) for x in (q, k, v))
    slab = min(QUERY_BLOCK, t)
    nb = -(-t // slab)
    q = jnp.pad(q, ((0, nb * slab - t), (0, 0), (0, 0)))

    @jax.checkpoint
    def some_queries(q, rows, k, v):
        # a padded query takes the last row's keys: it is sliced off
        mask = jnp.arange(t)[None] <= jnp.minimum(rows, t - 1)[:, None]
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        if od is not None:
            p = _rounded(p, od)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    o = jax.lax.map(lambda rows: some_queries(*rows, k, v), (
        q.reshape(nb, slab, heads, hd),
        jnp.arange(nb * slab).reshape(nb, slab)))
    return o.reshape(nb * slab, heads * hd)[:t]


def layer(params, b: str, h, cfg: dict, od=None):
    """A layer (parameters under the prefix ``b``) on one sequence,
    ``h`` [T, D]."""
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    t, heads = h.shape[0], cfg["num_attention_heads"]
    cos, sin = rotary_table(cfg["rope_theta"], hd, jnp.arange(t))
    x = _rms_norm(h, params[b + "n1/scale"], eps)
    q, k, v = (_mm("td,de->te", x, params[b + n + "/kernel"], od).reshape(
        t, heads, hd) for n in ("q", "k", "v"))
    o = attention(_rope(q, cos, sin), _rope(k, cos, sin), v, od)
    h = h + _rms_norm(_mm("te,ed->td", o, params[b + "o/kernel"], od),
                      params[b + "n2/scale"], eps)
    x = _rms_norm(h, params[b + "n3/scale"], eps)
    m = _gated(x, params[b + "gate/kernel"], params[b + "up/kernel"],
               params[b + "down/kernel"], od)
    return h + _rms_norm(m, params[b + "n4/scale"], eps)


def _stacked_layers(params, cfg: dict):
    return {key: jnp.stack([params[f"block{i}/{key}"]
                            for i in range(cfg["num_hidden_layers"])])
            for key in LAYER_KEYS}


def exit_probabilities(gates: Sequence[jax.Array]):
    """[p_1 .. p_R] from the gates [g_1 .. g_R] (the last is read by
    nothing): pass t takes its gate's share of what the earlier gates
    let through, the last pass what is left."""
    left, out = 1.0, []
    for g in gates[:-1]:
        out.append(left * g)
        left = left * (1.0 - g)
    return out + [left]


def passes_loss(per_pass: Sequence[Dict[str, jax.Array]], seq, cfg: dict,
                operand_dtype: Optional[str] = None):
    """The loss of ONE sequence, ``seq`` [T+1] int32, with pass t
    reading its stack, final norm, head and gate from ``per_pass[t]``
    (the embedding from the first). The model is ``[params] * R``. A
    layer's intermediates are computed again on the way back
    (``jax.checkpoint`` around each layer: the same arithmetic, twice);
    a pass's layers, all alike, run as one loop over their stacked
    weights (a scan: their code is compiled once a pass)."""
    od = None if operand_dtype is None else jnp.dtype(operand_dtype)
    eps = cfg["rms_norm_eps"]
    tokens, targets = seq[:-1], seq[1:]
    h = per_pass[0]["embed/embedding"][tokens]
    one = jax.checkpoint(lambda mine, h: layer(mine, "", h, cfg, od))
    stacks = {id(p): _stacked_layers(p, cfg) for p in per_pass}
    nll, gates = [], []
    for mine in per_pass:
        h, _ = jax.lax.scan(lambda h, w: (one(w, h), None), h,
                            stacks[id(mine)])
        h = _rms_norm(h, mine["norm/scale"], eps)
        logp = jax.nn.log_softmax(
            _mm("td,dv->tv", h, mine["head/kernel"], od), axis=-1)
        nll.append(-jnp.take_along_axis(logp, targets[:, None], -1)[:, 0])
        gates.append(jax.nn.sigmoid(
            jnp.einsum("td,do->t", h, mine["exit_gate/kernel"],
                       precision=HIGHEST) + mine["exit_gate/bias"][0]))
    p = exit_probabilities(gates)
    expected = sum(pt * nt for pt, nt in zip(p, nll))
    minus_entropy = sum(jax.scipy.special.xlogy(pt, pt) for pt in p)
    return jnp.mean(expected + ENTROPY_BETA * minus_entropy)


def sequence_loss(params: Dict[str, jax.Array], seq, cfg: dict,
                  operand_dtype: Optional[str] = None):
    return passes_loss([params] * cfg["total_ut_steps"], seq, cfg,
                       operand_dtype)


def loss_and_grads(params, toks, cfg: dict,
                   operand_dtype: Optional[str] = None):
    """``toks`` [S, T+1]: the mean over the sequences of each one's loss
    and gradient, a sequence at a time (every sequence has the same
    number of positions, so this is the batch's mean)."""
    grad = jax.value_and_grad(sequence_loss)

    def add(total, seq):
        return jax.tree_util.tree_map(
            jnp.add, total, grad(params, seq, cfg, operand_dtype)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, params))
    total, _ = jax.lax.scan(add, zero, toks)
    return jax.tree_util.tree_map(lambda s: s / toks.shape[0], total)


def live_score_entries(cfg: dict, seq_len: int) -> int:
    """Score entries the causal mask keeps, one sequence of T tokens,
    all heads, layers and passes: T(T+1)/2 a head and layer
    application."""
    return (cfg["num_attention_heads"] * cfg["num_hidden_layers"]
            * cfg["total_ut_steps"] * seq_len * (seq_len + 1) // 2)


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward pass of THIS SHARE requires per token at T
    tokens a sequence (multiply-add = 2):
        per layer APPLICATION (layers held x R passes)
                    q, k, v, o 4 * 2*D*H*hd; gate, up, down 3 * 2*D*W;
                    QK^T and PV over the LIVE score entries only, 4*hd
                    an entry: (T+1)/2 a head and token
        per pass    the head 2*D*V over the vocabulary rows held here
                    and the gate 2*D: R exits, each with its logits
    Masked score entries, the lookup, norms, rotary positions, softmax,
    SiLU and the exit distribution are not counted, nor anything
    computed a second time on the way back (every block and every exit
    is, in the program)."""
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    heads, w = cfg["num_attention_heads"], cfg["intermediate_size"]
    steps = cfg["total_ut_steps"]
    a_layer = 8 * d * heads * hd + 6 * d * w \
        + 4.0 * hd * heads * (seq_len + 1) / 2
    return steps * (cfg["num_hidden_layers"] * a_layer + 2 * d * v + 2 * d)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (twice forward) per token; nothing recomputed
    counts."""
    return 3 * forward_flops_per_token(cfg, seq_len)
