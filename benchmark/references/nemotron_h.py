"""Plain reference for the ``nemotron_h`` family: one rank's share of a
Nemotron-H decoder (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``model_type`` ``nemotron_h``) in straightforward ``jax.numpy``,
float32, matmuls at ``highest``.

No flax, no sort, no grouped matmul, no chunked scan, no kernel, nothing
taken from the program: the state-space recurrence runs a token at a
time, the attention is the ``[T, T]`` product per held head with the
mask written out (``references/kanana.attention``: a block of queries
against ALL keys at a time, so that 8,192 positions fit), the experts
are a loop over the experts held here with a mask. The weights are made
here from the seed (:func:`init_params`) under the path names the
program's parameter tree happens to use, and handed to both sides; the
router's correction bias is made here from the configuration
(:func:`correction_bias`) and handed to both sides too.

Equations (``n`` RMSNorm with a learned scale, eps
``layer_norm_epsilon``; HF ``modeling_nemotron_h``); every layer is
``x <- x + mixer(n(x))`` with the mixer its letter in
``hybrid_override_pattern`` names; ``a = n(x)``:
    ``M`` (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``, G =
    ``n_groups``, N = ``ssm_state_size``, K = ``conv_kernel``):
      (z, xBC) = a W_in [H P + (H P + 2 G N)];  dt = a W_dt [H]
          (HF: the last H columns of in_proj)
      xBC = silu(conv_K(xBC) + b_conv), depthwise, causal, K - 1 zeros
          before the first token
      (x, B, C) = split(xBC, [H P, G N, G N]); head i reads group
          i // (H / G)
      dt = softplus(dt + dt_bias);  A = -exp(A_log)     a head
      h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t      [P, N] a head
      y_t = h_t C_t + D x_t
      y = (y * silu(z)) / rms over each group's (H / G) P channels
          * scale [H P];  mixer = y W_out
    ``E``: s = sigmoid(a W_r) over ALL ``n_routed_experts``; the
      ``num_experts_per_tok`` largest of s + b (``n_group`` 1: no group
      limit); w_e = ``routed_scaling_factor`` * s_e / (sum of the chosen
      s + 1e-20); mixer = shared(a) + sum over the chosen e in
      ``local_experts`` of w_e * W_down_e(relu(W_up_e a)^2); shared: the
      same form at ``moe_shared_expert_intermediate_size``, unweighted
    ``*``: q = a W_q [heads x hd], k = a W_k, v = a W_v [kv heads x hd];
      o = softmax(q k^T / sqrt(hd), key j <= query i) v, NO positional
      term; mixer = o W_o
    logits = norm_f(x) W_head          (the vocabulary rows held here)
    loss = mean next-token cross-entropy; no auxiliary loss

The share: ``mamba_heads`` with ``mamba_groups`` (whole groups, so B, C
and the gated norm's statistics are the group's own), ``query_heads``
with the ``key_value_heads`` they read (held query i reads held
key/value head i // (queries held / key/value heads held)),
``local_experts``: ``W_in``, ``W_dt``, the conv, ``W_q``, ``W_k``,
``W_v`` have the held columns only, ``W_out`` and ``W_o`` their rows,
and ``y W_out`` / ``o W_o`` are their part of the sum over heads; the
router keeps its published width and its top-k of all experts, only the
experts in ``local_experts`` are computed; nothing stands in for what
other ranks would add.

``operand_dtype`` is the control of ``correct``: the same mathematics
with every operand of a matmul that the configuration runs in its
compute dtype rounded to that type first (an 8-bit float with a scale
per tensor): the projections, the recurrence's two products, the
attention's two, the experts, the head. ``W_dt`` and the router product
are float32 in the configuration and stay so in the control. ``None``
is the reference itself.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# dense causal attention in query blocks and the sigmoid-plus-bias router
# are Kanana's; the norm is Laguna's
from benchmark.references.kanana import attention, router_weights
from benchmark.references.laguna import _rms_norm
# the rounded-operand matmul of the control is the families' common one
from benchmark.references.transformer import _mm

INIT_STD = 0.02     # assumed: initializer_range
REMAT_TOKENS = 64   # the recurrence keeps its state this many tokens apart
BIAS = "e_score_correction_bias"
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _held(cfg: dict) -> Tuple[int, int, int, int]:
    """(Mamba heads, their groups, query heads, key/value heads) held
    here."""
    return tuple(cfg[key][1] - cfg[key][0] for key in (
        "mamba_heads", "mamba_groups", "query_heads", "key_value_heads"))


def layer_shapes(cfg: dict, kind: str) -> Dict[str, Tuple[int, ...]]:
    """The parameters of one layer of ``kind``, without its prefix."""
    d, n = cfg["hidden_size"], cfg["ssm_state_size"]
    h, g, hq, hkv = _held(cfg)
    inner, hd = h * cfg["mamba_head_dim"], cfg["head_dim"]
    held = cfg["local_experts"][1] - cfg["local_experts"][0]
    w, ws = (cfg["moe_intermediate_size"],
             cfg["moe_shared_expert_intermediate_size"])
    conv = inner + 2 * g * n
    return {"norm/scale": (d,), **{
        MAMBA: {
            "in_proj/kernel": (d, inner + conv), "dt_proj": (h, d),
            "conv": (cfg["conv_kernel"], conv), "conv_bias": (conv,),
            "A_log": (h,), "D": (h,), "dt_bias": (h,),
            "gated_norm": (inner,), "out_proj/kernel": (inner, d)},
        EXPERTS: {
            "router/kernel": (d, cfg["n_routed_experts"]),
            "shared_up/kernel": (d, ws), "shared_down/kernel": (ws, d),
            "w_up": (held, d, w), "w_down": (held, w, d)},
        ATTENTION: {
            "q_proj/kernel": (d, hq * hd), "k_proj/kernel": (d, hkv * hd),
            "v_proj/kernel": (d, hkv * hd), "o_proj/kernel": (hq * hd, d)},
    }[kind]}


def vocab_rows(cfg: dict) -> int:
    """Rows of the embedding and the head held here: ``vocab_rows``
    where the file gives it beside ``vocab_size``, which the harness
    reads as the range its data draws token ids from, else that."""
    return cfg.get("vocab_rows", cfg["vocab_size"])


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    d, v = cfg["hidden_size"], vocab_rows(cfg)
    shapes = {"embed/embedding": (v, d)}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        shapes.update({f"block{i}/{name}": shape for name, shape
                       in layer_shapes(cfg, kind).items()})
    shapes.update({"norm_f/scale": (d,), "head/kernel": (d, v)})
    return shapes


def num_params(cfg: dict) -> int:
    return sum(math.prod(shape) for shape in param_shapes(cfg).values())


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Every TRAINED weight from the seed in ONE jitted call on the
    default device, float32: matrices, expert stacks and the embedding
    normal(0, 0.02); RMSNorm scales, the gated norm's and ``D`` 1; the
    convolution and its bias uniform(-1/2, 1/2) (a 4-tap filter's
    fan-in bound, what HF leaves it at); ``A_log`` = log(1 + the head's
    index in the WHOLE model); ``dt_bias`` the inverse softplus of a
    step drawn log-uniform in [``time_step_min``, ``time_step_max``] and
    floored at ``time_step_floor``, as HF's ``_init_weights`` sets
    them. The correction bias is no weight: :func:`correction_bias`."""
    shapes = param_shapes(cfg)
    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            leaf = name.rsplit("/", 1)[-1]
            if leaf in ("scale", "gated_norm", "D"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif leaf == "A_log":
                out[name] = jnp.log(1.0 + cfg["mamba_heads"][0] + jnp.arange(
                    shape[0], dtype=jnp.float32))
            elif leaf == "dt_bias":
                step = jnp.maximum(jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, lo, hi)), cfg["time_step_floor"])
                out[name] = step + jnp.log(-jnp.expm1(-step))
            elif leaf in ("conv", "conv_bias"):
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


def correction_bias(cfg: dict) -> Dict[str, np.ndarray]:
    """``{block<l>/e_score_correction_bias: [n_routed_experts] float32}``
    for the expert layers: a constant of the configuration (its group
    ``e_score_correction_bias``: a seed and a standard deviation), not
    of the run's seed and not trained (Kanana's rule: the published
    balance rule acts outside the loss; a checkpoint carries the values
    it ended at, and these stand for them)."""
    spec = cfg[BIAS]
    return {f"block{i}/{BIAS}": np.random.default_rng(
        [spec["seed"], i]).normal(0.0, spec["std"],
                                  cfg["n_routed_experts"]).astype(np.float32)
        for i, kind in enumerate(cfg["hybrid_override_pattern"])
        if kind == EXPERTS}


def token_recurrence(x, dt, a, b, c, od=None):
    """The state-space recurrence a token at a time: ``x`` [T, H, P],
    ``dt`` [T, H], ``a`` [H], ``b``, ``c`` [T, H, N] (a head's group's)
    -> ``h_t C_t`` [T, H, P]. A ``lax.scan`` over the tokens inside a
    scan over blocks of ``REMAT_TOKENS`` of them; a block's states are
    computed again on the way back (``jax.checkpoint``: the same
    arithmetic, twice), so that T / 64 + 64 states are alive and not
    T."""
    t, h, p = x.shape

    def token(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + _mm("hp,hn->hpn", dt_t[:, None] * x_t, b_t, od)
        return s, _mm("hpn,hn->hp", s, c_t, od)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    xs = (x, dt, b, c)
    whole = t - t % REMAT_TOKENS
    s = jnp.zeros((h, p, b.shape[-1]), jnp.float32)
    out = []
    if whole:
        s, y = jax.lax.scan(block, s, tuple(
            v[:whole].reshape((-1, REMAT_TOKENS) + v.shape[1:]) for v in xs))
        out.append(y.reshape((whole,) + y.shape[2:]))
    if t > whole:
        out.append(block(s, tuple(v[whole:] for v in xs))[1])
    return jnp.concatenate(out)


def mamba_mixer(params, b: str, a, cfg: dict, od=None):
    """The Mamba-2 mixer on one sequence's normed input ``a`` [T, D],
    parameters under the prefix ``b``."""
    p, n = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    h, g, _, _ = _held(cfg)
    t, inner = a.shape[0], h * p
    zxbc = _mm("td,de->te", a, params[b + "in_proj/kernel"], od)
    z, xbc = zxbc[:, :inner], zxbc[:, inner:]
    step = _mm("td,hd->th", a, params[b + "dt_proj"], None)
    taps = params[b + "conv"]
    before = jnp.concatenate(
        [jnp.zeros((taps.shape[0] - 1, xbc.shape[1]), xbc.dtype), xbc])
    xbc = jax.nn.silu(sum(taps[j] * before[j:j + t]
                          for j in range(taps.shape[0]))
                      + params[b + "conv_bias"])
    x = xbc[:, :inner].reshape(t, h, p)
    # head i reads group i // (H / G)
    of = jnp.arange(h) // (h // g)
    bmat = xbc[:, inner:inner + g * n].reshape(t, g, n)[:, of]
    cmat = xbc[:, inner + g * n:].reshape(t, g, n)[:, of]
    dt = jax.nn.softplus(step + params[b + "dt_bias"])
    y = token_recurrence(x, dt, -jnp.exp(params[b + "A_log"]), bmat, cmat,
                         od) + params[b + "D"][:, None] * x
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    y = y.reshape(t, inner) * params[b + "gated_norm"]
    return _mm("te,ed->td", y, params[b + "out_proj/kernel"], od)


def attention_mixer(params, b: str, a, cfg: dict, od=None):
    """``o W_o`` of the held query heads for one sequence, no positional
    term anywhere."""
    hd, t = cfg["head_dim"], a.shape[0]
    _, _, hq, hkv = _held(cfg)
    q = _mm("td,de->te", a, params[b + "q_proj/kernel"], od).reshape(
        t, hq, hd)
    # held query i reads held key/value head i // (hq / hkv)
    of = jnp.arange(hq) // (hq // hkv)
    k, v = (_mm("td,de->te", a, params[b + name], od).reshape(
        t, hkv, hd)[:, of] for name in ("k_proj/kernel", "v_proj/kernel"))
    return _mm("te,ed->td", attention(q, k, v, od),
               params[b + "o_proj/kernel"], od)


def _relu2_ffn(m, up, down, od):
    return _mm("tw,wd->td",
               jnp.square(jax.nn.relu(_mm("td,dw->tw", m, up, od))), down,
               od)


def expert_mixer(params, b: str, m, bias, cfg: dict, od=None):
    """The shared expert and the held routed experts' terms, every held
    expert applied to every row and masked by the router's choice."""
    chosen, weights = router_weights(
        m, params[b + "router/kernel"], bias, cfg["num_experts_per_tok"],
        cfg["routed_scaling_factor"])
    y = _relu2_ffn(m, params[b + "shared_up/kernel"],
                   params[b + "shared_down/kernel"], od)
    lo, hi = cfg["local_experts"]

    def add_expert(y, held):
        e, up, down = held
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        return y + weight[:, None] * _relu2_ffn(m, up, down, od), None

    # a loop over the experts held here, one at a time (a scan: the
    # experts' code is compiled once, not once an expert)
    y, _ = jax.lax.scan(add_expert, y, (
        jnp.arange(lo, hi), params[b + "w_up"], params[b + "w_down"]))
    return y


def layer(params, b: str, x, kind: str, bias, cfg: dict, od=None):
    """A layer of ``kind`` (parameters under the prefix ``b``; ``bias``
    an expert layer's correction bias) on one sequence, ``x`` [T, D]."""
    a = _rms_norm(x, params[b + "norm/scale"], cfg["layer_norm_epsilon"])
    if kind == MAMBA:
        return x + mamba_mixer(params, b, a, cfg, od)
    if kind == ATTENTION:
        return x + attention_mixer(params, b, a, cfg, od)
    return x + expert_mixer(params, b, a, bias, cfg, od)


def sequence_logits(params: Dict[str, jax.Array], tokens, cfg: dict,
                    operand_dtype: Optional[str] = None):
    """The logits of ONE sequence, ``tokens`` [T] int32 -> [T, V]. A
    layer's intermediates are computed again on the way back
    (``jax.checkpoint`` around each layer: the same arithmetic,
    twice)."""
    od = None if operand_dtype is None else jnp.dtype(operand_dtype)
    x = params["embed/embedding"][tokens]
    biases = correction_bias(cfg)
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        b = f"block{i}/"
        mine = {n: p for n, p in params.items() if n.startswith(b)}
        x = jax.checkpoint(lambda mine, x, b=b, kind=kind: layer(
            mine, b, x, kind, biases.get(b + BIAS), cfg, od))(mine, x)
    x = _rms_norm(x, params["norm_f/scale"], cfg["layer_norm_epsilon"])
    return _mm("td,dv->tv", x, params["head/kernel"], od)


def sequence_loss(params: Dict[str, jax.Array], toks, cfg: dict,
                  operand_dtype: Optional[str] = None):
    """The loss of ONE sequence, ``toks`` [T+1] int32."""
    logp = jax.nn.log_softmax(
        sequence_logits(params, toks[:-1], cfg, operand_dtype), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, toks[1:, None], axis=-1))


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
    """Score entries the causal mask keeps, one sequence, all attention
    layers and held query heads: T(T+1)/2 a head."""
    return (cfg["hybrid_override_pattern"].count(ATTENTION) * _held(cfg)[2]
            * seq_len * (seq_len + 1) // 2)


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward pass of THIS SHARE requires per token at
    sequence length T (multiply-add = 2):
        ``M`` layer  W_in 2*D*(2*H*P + 2*G*N); W_dt 2*D*H; the TOKEN
                     recurrence's two products a head, x (x) B into the
                     state and the state under C, 2 * 2*P*N (the
                     chunked form's extra products are not required);
                     W_out 2*H*P*D
        ``E`` layer  router 2*D*E; shared expert 2 * 2*D*W_shared (two
                     matmuls: no gate); the rows a token sends to the
                     experts held here, at their EXPECTED number under
                     even routing, k * E_local / E, each 2 * 2*D*W
        ``*`` layer  q 2*D*Hq*hd; k, v 2*D*Hkv*hd each; o 2*Hq*hd*D;
                     QK^T and PV over the LIVE score entries only,
                     2*hd an entry each (a head's mean context is
                     (T+1)/2)
        head         2*D*V over the vocabulary rows held here
    Under skewed routing the rows routed here differ from the
    expectation: ``nemotron.local_row_share`` reports them, and the
    count stays what even routing requires. Masked score entries,
    lookups, norms, the convolution (8 operations a channel), decays,
    softplus, sigmoids, softmax, activations, the skip term and the
    combine are not counted, nor anything computed a second time on the
    way back."""
    d, v = cfg["hidden_size"], vocab_rows(cfg)
    p, n, hd = cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["head_dim"]
    h, g, hq, hkv = _held(cfg)
    lo, hi = cfg["local_experts"]
    rows = cfg["num_experts_per_tok"] * (hi - lo) / cfg["n_routed_experts"]
    pattern = cfg["hybrid_override_pattern"]
    mamba = 2 * d * (2 * h * p + 2 * g * n) + 2 * d * h \
        + h * 4 * p * n + 2 * h * p * d
    experts = 2 * d * cfg["n_routed_experts"] \
        + 4 * d * cfg["moe_shared_expert_intermediate_size"] \
        + rows * 4 * d * cfg["moe_intermediate_size"]
    attn = 2 * d * hd * (2 * hq + 2 * hkv)
    return (pattern.count(MAMBA) * mamba + pattern.count(EXPERTS) * experts
            + pattern.count(ATTENTION) * attn
            + 4.0 * hd * live_score_entries(cfg, seq_len) / seq_len
            + 2 * d * v)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (twice forward); nothing recomputed counts."""
    return 3 * forward_flops_per_token(cfg, seq_len)
