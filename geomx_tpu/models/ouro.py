"""Ouro, a looped language model (ByteDance/Ouro-2.6B, ``ouro``;
"Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): one stack of dense decoder layers whose SAME weights
are applied ``total_ut_steps`` = R times a forward pass, an exit gate
after every pass of the stack, and a loss over all R exits.

Layer equations (``N*`` RMSNorm with a learned scale; the residual
stream float32). A block has sandwich norms, four a layer:

    a = Attn(N1(h));  h = h + N2(a)
    m = Wd (silu(Wg N3(h)) * Wu N3(h));  h = h + N4(m)
    Attn: q, k, v = x Wq, x Wk, x Wv, H heads of hd, no grouping, no
          bias; rotary positions on all hd dims (half-split pairing);
          o_h = causal softmax(q_h k_h^T / sqrt(hd)) v_h in float32; Wo

The model, ``Stack`` the held layers and ``Nf`` the final norm, the same
every pass, the NORMED state carried into the next pass:

    h_0 = E[tokens]
    t = 1..R:  h_t = Nf(Stack(h_{t-1}));  logits_t = h_t W_head
               g_t = sigmoid(h_t w_g + b_g)      one map D -> 1

The loss (:func:`looped_exit_loss`; the paper's stage-I objective: the
expected task loss under the exit distribution with an entropy
regulariser, a uniform prior over the exits), per position:

    p_1 = g_1;  p_t = g_t prod_{j<t} (1 - g_j), 1 < t < R
    p_R = prod_{j<R} (1 - g_j)
    L = mean over positions of [ sum_t p_t nll_t - beta H(p) ]
    H(p) = -sum_t p_t log p_t

Form: the R passes are ONE ``scan`` with the parameters broadcast, so a
program holds each block once whatever R, and the way back sums the R
contributions to every weight's cotangent in the loop's carry. Its body
is the stack, ``Nf``, the head, the gate and the per-position ``nll_t``;
it hands out ``nll_t`` and the gate's logit, [R, B, T] each, and a
pass's logits never outlive its iteration. Every block is
rematerialised (``nn.remat``, as ``models/kanana.py``), and so is the
exit: what the loop keeps a pass is each block's input and the exit's.
The attention core is ``transformer.causal_core`` after
``transformer.rotary``: the flash kernels where ``runs_kernel`` says so.

The model holds a rank's share of the vocabulary (``vocab`` rows of
the embedding and the head); the layers are whole.

Precision: parameters float32, matmul operands in ``compute_dtype``;
float32 for the residual stream, every norm's statistics, rotary
angles, the attention scores and their softmax, the logits, the gate
(its product at ``highest``), the exit distribution and the loss.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from geomx_tpu.models.transformer import (HIGHEST, RMSNorm, causal_core,
                                          kernel_score_entries, rotary,
                                          rotary_frequencies, runs_kernel,
                                          score_entries)

__all__ = ["Ouro", "OuroBlock", "exit_distribution", "looped_exit_loss"]

ENTROPY_BETA = 0.05     # the entropy term's weight in the stage-I loss


class OuroBlock(nn.Module):
    dim: int
    heads: int
    head_dim: int
    width: int
    rope_theta: float
    eps: float = 1e-6
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        """``h`` [B, T, D] float32 -> h'."""
        dt = self.compute_dtype
        b, t, d = h.shape
        heads, hd = self.heads, self.head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=dt)
        with jax.named_scope("attention_full"):
            x = RMSNorm(self.eps, dt, name="n1")(h)
            q, k, v = (dense(heads * hd, name=n)(x).reshape(b, t, heads, hd)
                       for n in ("q", "k", "v"))
            inv_freq, factor = rotary_frequencies(
                {"rope_type": "default", "rope_theta": self.rope_theta}, hd)
            q, k = rotary(q, inv_freq, factor), rotary(k, inv_freq, factor)
            with jax.named_scope("causal_core"):
                o = causal_core(q)(q, k, v)
            a = dense(d, name="o")(o.reshape(b, t, heads * hd))
            h = h + RMSNorm(self.eps, jnp.float32, name="n2")(a)
        with jax.named_scope("dense_ffn"):
            x = RMSNorm(self.eps, dt, name="n3")(h)
            m = dense(d, name="down")(
                nn.silu(dense(self.width, name="gate")(x))
                * dense(self.width, name="up")(x))
            h = h + RMSNorm(self.eps, jnp.float32, name="n4")(m)
        return h


class Ouro(nn.Module):
    vocab: int                  # rows held here
    dim: int
    depth: int                  # layers of the stack held here
    steps: int                  # R: passes of the stack a forward pass
    heads: int
    head_dim: int
    width: int
    rope_theta: float
    eps: float = 1e-6
    compute_dtype: Any = jnp.float32

    def counts(self, batch: int, t: int, kernel: bool = False):
        """What a pass over ``batch`` sequences of ``t`` positions has
        by shape: (layer applications, live score entries, computed
        score entries), the entries over all heads, layers and passes;
        ``kernel``: the cores run as the kernel
        (``transformer.runs_kernel``), which computes its live blocks."""
        live, computed = score_entries(t)
        if kernel:
            computed = kernel_score_entries(t, self.head_dim)
        applications = batch * self.depth * self.steps
        return (applications, applications * self.heads * live,
                applications * self.heads * computed)

    @nn.compact
    def __call__(self, tokens, targets=None):
        """``tokens`` [B, T] -> (per pass, [R, B, T, vocab] float32
        logits, or with ``targets`` [B, T] their [R, B, T] negative
        log-likelihoods; the gate's logits [R, B, T] float32)."""
        dt = self.compute_dtype

        def exit_of(_mdl, h):
            h = RMSNorm(self.eps, jnp.float32, name="norm")(h)
            logits = nn.Dense(
                self.vocab, use_bias=False, dtype=dt,
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32),
                name="head")(h)
            gate = nn.Dense(1, dtype=jnp.float32, precision=HIGHEST,
                            name="exit_gate")(h)[..., 0]
            if targets is None:
                return h, logits, gate
            nll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                                       targets[..., None], axis=-1)[..., 0]
            return h, nll, gate

        def one_pass(mdl, h, _):
            block = nn.remat(OuroBlock)
            for i in range(self.depth):
                h = block(self.dim, self.heads, self.head_dim, self.width,
                          self.rope_theta, self.eps, dt,
                          name=f"block{i}")(h)
            with jax.named_scope("ouro_exit"):
                h, out, gate = nn.remat(exit_of)(mdl, h)
            return h, (out, gate)

        h = nn.Embed(self.vocab, self.dim, name="embed")(tokens)
        with jax.named_scope("ouro_loop"):
            _, outs = nn.scan(
                one_pass, variable_broadcast="params",
                split_rngs={"params": False}, length=self.steps)(
                    self, h, None)
        return outs


def exit_distribution(gate_logits):
    """``gate_logits`` [R, ...] -> (p, log p) [R, ...]: pass t exits
    with its gate's probability of what the earlier gates let through,
    the last pass takes what is left. In logs, so that a gate that has
    saturated (p_t rounds to 0) gives ``p_t log p_t`` = 0 and no NaN."""
    log_g = jax.nn.log_sigmoid(gate_logits)
    log_stay = jax.nn.log_sigmoid(-gate_logits)
    # what reaches pass t: sum_{j<t} log(1 - g_j)
    reach = jnp.cumsum(log_stay, 0) - log_stay
    log_p = jnp.concatenate([reach[:-1] + log_g[:-1], reach[-1:]], 0)
    return jnp.exp(log_p), log_p


def looped_exit_loss(model, variables, toks, beta: float = ENTROPY_BETA):
    """``toks`` [B, T+1]: the expected next-token loss under the exit
    distribution less ``beta`` times its entropy, the mean over the
    positions (the module's docstring has the equations). Returns
    (loss, [positions, ``model.counts``, the exit mass of each pass
    (the sum over positions of p_t), the sum of each pass's nll_t]),
    the counts as float32: 4 + 2R of them."""
    nll, gate = model.apply(variables, toks[:, :-1], toks[:, 1:])
    p, log_p = exit_distribution(gate)
    loss = jnp.mean(jnp.sum(p * (nll + beta * log_p), 0))
    by_shape = model.counts(toks.shape[0], toks.shape[1] - 1,
                            runs_kernel(toks[:, :-1]))
    return loss, jnp.concatenate([
        jnp.asarray([nll[0].size, *by_shape], jnp.float32),
        jnp.sum(p, (1, 2)), jnp.sum(nll, (1, 2))])
