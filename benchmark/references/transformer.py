"""Plain reference for the ``transformer`` family: a GPT-2 style decoder
in straightforward ``jax.numpy``, float32, matmuls at ``highest``.

No flax, no kernels, nothing taken from the program: the weights are
made here from the seed (:func:`init_params`), under the path names the
program's parameter tree happens to use, and handed to both sides.

Equations (Radford et al. 2019, as in ``openai-community/gpt2``):
    x   = wte[tokens] + wpe[positions]
    per block:  h = LN(x); q,k,v = split(h @ Wqkv + b); causal softmax
                attention per head, scale 1/sqrt(head); x += o @ Wproj + b
                h = LN(x); x += gelu_tanh(h @ Wup + b) @ Wdown + b
    logits = LN(x) @ Whead + bhead
    loss   = mean over positions of -log softmax(logits)[next token]

Departures from the published model, each mirrored from the program's
``models/transformer.py`` (it is run as it is): the output head is its
own matrix with a bias (GPT-2 ties it to ``wte`` and has none), the
LayerNorm epsilon is the configuration file's (1e-6 as run, published
1e-5), no dropout.

``operand_dtype`` is the control of ``correct``: the same mathematics
with every matmul operand rounded to that type first (an 8-bit float
with a scale per tensor). ``None`` is the reference itself.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02     # GPT-2's initializer_range


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    d, v, p = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    shapes = {"embed/embedding": (v, d), "pos/embedding": (p, d)}
    for i in range(cfg["n_layer"]):
        b = f"block{i}/"
        shapes.update({
            b + "ln1/scale": (d,), b + "ln1/bias": (d,),
            b + "qkv/kernel": (d, 3 * d), b + "qkv/bias": (3 * d,),
            b + "proj/kernel": (d, d), b + "proj/bias": (d,),
            b + "ln2/scale": (d,), b + "ln2/bias": (d,),
            b + "up/kernel": (d, 4 * d), b + "up/bias": (4 * d,),
            b + "down/kernel": (4 * d, d), b + "down/bias": (d,)})
    shapes.update({"lnf/scale": (d,), "lnf/bias": (d,),
                   "head/kernel": (d, v), "head/bias": (v,)})
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
    device, float32: matrices and embeddings normal(0, 0.02), biases 0,
    LayerNorm scales 1 (GPT-2's own initialisation)."""
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


def _rounded(x, od):
    """``x`` rounded to ``od`` and back, with the identity as its
    derivative (a cast's own derivative would round the cotangent to
    ``od`` too, and an unscaled 8-bit cotangent underflows to zero). An
    8-bit float gets a scale per tensor, as a later PR would use it: the
    largest magnitude maps to the type's largest value."""
    if jnp.dtype(od).itemsize > 1:
        r = x.astype(od).astype(jnp.float32)
    else:
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(
            jnp.finfo(od).max)
        r = (x / s).astype(od).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(r - x)


def _mm(eq: str, a, b, od):
    if od is not None:
        a, b = _rounded(a, od), _rounded(b, od)
    # rounded operands multiply exactly in float32 at ``highest``, so
    # the control differs from the reference by the rounding alone
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _layer_norm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def forward(params: Dict[str, jax.Array], tokens, cfg: dict,
            operand_dtype: Optional[str] = None):
    """Logits [B, T, vocab] in float32 for ``tokens`` [B, T] int32."""
    od = None if operand_dtype is None else jnp.dtype(operand_dtype)
    d, h = cfg["n_embd"], cfg["n_head"]
    eps = cfg["layer_norm_epsilon"]
    B, T = tokens.shape
    x = params["embed/embedding"][tokens] + params["pos/embedding"][:T][None]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(cfg["n_layer"]):
        b = f"block{i}/"
        y = _layer_norm(x, params[b + "ln1/scale"], params[b + "ln1/bias"],
                        eps)
        qkv = _mm("btd,de->bte", y, params[b + "qkv/kernel"], od) \
            + params[b + "qkv/bias"]
        q, k, v = (z.reshape(B, T, h, d // h)
                   for z in jnp.split(qkv, 3, axis=-1))
        s = _mm("bqhd,bkhd->bhqk", q, k, od) / jnp.sqrt(
            jnp.float32(d // h))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = _mm("bhqk,bkhd->bqhd", p, v, od).reshape(B, T, d)
        x = x + _mm("btd,de->bte", o, params[b + "proj/kernel"], od) \
            + params[b + "proj/bias"]
        y = _layer_norm(x, params[b + "ln2/scale"], params[b + "ln2/bias"],
                        eps)
        y = _gelu_tanh(_mm("btd,de->bte", y, params[b + "up/kernel"], od)
                       + params[b + "up/bias"])
        x = x + _mm("btd,de->bte", y, params[b + "down/kernel"], od) \
            + params[b + "down/bias"]
    x = _layer_norm(x, params["lnf/scale"], params["lnf/bias"], eps)
    return _mm("btd,dv->btv", x, params["head/kernel"], od) \
        + params["head/bias"]


def loss_fn(params, toks, cfg: dict, operand_dtype: Optional[str] = None):
    """Mean next-token cross-entropy; ``toks`` is [B, T+1]."""
    logits = forward(params, toks[:, :-1], cfg, operand_dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1))


def loss_and_grads(params, toks, cfg: dict,
                   operand_dtype: Optional[str] = None):
    return jax.value_and_grad(loss_fn)(params, toks, cfg, operand_dtype)


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward pass REQUIRES per token at sequence length
    T (multiply-add = 2):
        per layer   qkv 2*D*3D + proj 2*D*D + up 2*D*4D + down 2*4D*D
                    = 24*D^2; attention QK^T and PV over the causal
                    context, whose mean length is (T+1)/2: 4*D*(T+1)/2
        head        2*D*V
    Embedding lookups, LayerNorm, softmax, GELU and the bias adds are
    not counted. The dense attention the cells run executes the full
    T x T product: the masked half is the kernel's waste, not the
    model's need, so it is not in the numerator of ``step.busy_mfu``."""
    d, v, layers = cfg["n_embd"], cfg["vocab_size"], cfg["n_layer"]
    return layers * (24 * d * d + 4 * d * (seq_len + 1) / 2) + 2 * d * v


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (twice forward); nothing recomputed counts."""
    return 3 * forward_flops_per_token(cfg, seq_len)
