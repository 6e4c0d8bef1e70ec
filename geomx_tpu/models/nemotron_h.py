"""Nemotron-H decoder (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B,
``model_type`` ``nemotron_h``): every layer is ONE mixer behind one
norm, and the layer's letter in ``hybrid_override_pattern`` says which:
``M`` a Mamba-2 state-space mixer, ``E`` a shared expert beside top-k
routed experts, none of them gated, under a sigmoid router whose CHOICE
reads a correction bias, ``*`` grouped-query attention with no
positional term at all.

Layer equations (``n`` RMSNorm with a learned scale, eps
``layer_norm_epsilon``; the residual stream float32; HF
``modeling_nemotron_h``):

    x = x + mixer(n(x));  a = n(x)

``M``, Mamba-2: H heads of P, G groups of H / G heads, state N, conv
kernel K with a bias, no bias on the projections:
    (z, xBC) = a W_in [H P + (H P + 2 G N)];  dt = a W_dt [H]
    xBC = silu(conv_K(xBC) + b_conv)    depthwise and causal along the
          sequence, K - 1 zeros before the first token
    (x, B, C) = split(xBC, [H P, G N, G N]);  x a head [P], B and C a
          group [N]; a head reads its group's
    dt = softplus(dt + dt_bias) a head (``time_step_limit`` (0, inf): no
          clamp);  A = -exp(A_log) a head
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t    a [P, N] state a head
    y_t = h_t C_t + D x_t                           ``ops/ssd.py``
    y = rmsnorm_grouped(y * silu(z))   statistics over each group's
          (H / G) P channels, a learned scale of H P
    mixer = y W_out
``E``, experts:
    s = sigmoid(a W_r) over ALL experts, float32
    the top-k by s + b   (b: ``e_score_correction_bias``; ``n_group`` 1)
    w_e = routed_scale * s_e / (sum_{chosen} s + 1e-20)
    mixer = shared(a) + sum_{chosen e held here} w_e expert_e(a)
    an expert and the shared one: W_down(relu(W_up a)^2), NOT gated
``*``, attention: Hq query heads over Hkv key/value heads of hd, no
    bias, no rotary or other positional term (the Mamba layers carry
    position):
    o = softmax(q k^T / sqrt(hd), key j <= query i) v;  mixer = o W_o
logits = norm_f(x) W_head     (untied)

``b`` is no parameter: the published balance rule moves it outside the
loss, no gradient reaches it, and it lives in the variable collection
``buffers`` (``moe.biased_sigmoid_router``), as Kanana's does.

The model is one rank's share of a tensor- and expert-parallel layout,
as ``models/laguna.py`` and ``models/kanana.py`` take it. A block is
told what it holds: ``mamba_heads`` with the ``mamba_groups`` they read
(whole groups: a group's heads share its B and C and its gated norm's
statistics, so with whole groups held no statistic crosses ranks and the
Mamba share is exact), ``query_heads`` with the ``kv_heads`` they read
(a key/value head may serve more query heads than are held here: every
rank that holds some of its queries holds it), ``local_experts``. The
rank computes its heads' part of ``y W_out`` / ``o W_o`` and its
experts' terms for the rows routed to them; what other ranks would add
is left out and nothing stands in for it. Norms, router and shared
expert are whole.

Memory: every block is computed again on the way back (``nn.remat``: a
block keeps its input and nothing else, ``models/kanana.py``'s plan),
and the loss takes the head ``HEAD_ROWS`` positions at a time, each
block of rows computed again on the way back (as ``models/ouro.py``'s
exits are): a pass's [T, vocab] float32 logits, their softmax and their
cotangent are 1.6 GB at 8,192 positions over 16,384 rows, and two flat
trainers leave no room for them (PERF.md section 4 has the readings).
So the family keeps its own :func:`next_token_loss`, which hands the
model the targets and takes the positions' negative log-likelihoods.

Precision: parameters float32, matmul operands in ``compute_dtype``;
float32 for the residual stream, every norm's statistics, what decides
the decay (``W_dt`` at ``highest``, ``dt_bias``, softplus, ``A``, the
scan's running sums, exponentials and states), the convolution, the
attention scores and their softmax, the logits, and everything that
decides routing. ``W_dt`` is a matrix of its own for that reason: HF
holds it as the last H columns of ``in_proj``; the parameters are the
same in number.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from geomx_tpu.models.moe import (biased_sigmoid_router, plain_experts,
                                  sparse_dispatch)
from geomx_tpu.models.qwen3_next import causal_conv
from geomx_tpu.models.transformer import (HIGHEST, RMSNorm, causal_core,
                                          kernel_score_entries, runs_kernel,
                                          score_entries)
from geomx_tpu.ops.ssd import chunks_of, ssd_chunked

__all__ = ["NemotronH", "NemotronHBlock", "next_token_loss", "relu2"]

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# positions whose logits the loss holds at once (the module's ``Memory``)
HEAD_ROWS = 1024
# columns XLA's grouped matmul on the chip takes at a time: a routed
# expert's width is filled up to a whole number of them (``_filled``)
EXPERT_COLUMNS = 512


def relu2(x):
    """``mlp_hidden_act`` ``relu2``: the squared ReLU."""
    return jnp.square(nn.relu(x))


def _filled(width: int) -> int:
    """Columns of zeros beside a routed expert's ``width`` inside the
    step: up to the next multiple of ``EXPERT_COLUMNS`` where that is at
    most an eighth more, else none. ``relu2(0) = 0`` and the rows of
    ``w_down`` they meet are zeros, so no result or gradient changes;
    the parameters keep the published width. At 1,856 columns (3.6
    of them) every grouped product reads 7-10% of the chip's peak, at
    2,048 14-23%: the routed experts' time a round halves, and with it
    what a round swings by as the router's share for the held experts
    drifts (PERF.md section 6, PR 65)."""
    fill = -width % EXPERT_COLUMNS
    return fill if 8 * fill <= width else 0


def _held(heads: Tuple[int, int], read: Tuple[int, int], what: str):
    """(heads held, heads a head of ``read`` serves here)."""
    n, m = heads[1] - heads[0], read[1] - read[0]
    if n <= 0 or m <= 0 or n % m:
        raise ValueError(f"{what}: the heads {heads} are not shared "
                         f"evenly by the {read} they read")
    return n, n // m


class NemotronHBlock(nn.Module):
    dim: int
    kind: str                       # "M" | "E" | "*"
    mamba_head_dim: int             # P
    state_size: int                 # N
    conv_kernel: int
    chunk: int
    mamba_heads: Tuple[int, int]    # held here, of the layer's H
    mamba_groups: Tuple[int, int]   # the groups they read, whole
    head_dim: int
    query_heads: Tuple[int, int]    # held here
    kv_heads: Tuple[int, int]       # the key/value heads they read
    num_experts: int
    experts_per_token: int
    expert_width: int
    shared_width: int
    local_experts: Tuple[int, int]
    routed_scale: float
    eps: float = 1e-5
    compute_dtype: Any = jnp.float32

    def _mamba(self, a):
        dt = self.compute_dtype
        b, t, _ = a.shape
        p, n = self.mamba_head_dim, self.state_size
        h, r = _held(self.mamba_heads, self.mamba_groups, "mamba")
        g, inner = h // r, h * p
        dense = partial(nn.Dense, use_bias=False, dtype=dt)
        with jax.named_scope("mamba_mixer"):
            z, xbc = jnp.split(dense(2 * inner + 2 * g * n, name="in_proj")(
                a), [inner], axis=-1)
            # what decides the decay is float32, as what decides routing
            # is: a token's dt A is summed over a chunk and exponentiated
            # (held [H, D], D along the lanes: a [D, 8] leaf makes the
            # chip's compiler view the trainer's whole flat vector as
            # [n / 8, 8] to cut it out, sixteen times its bytes)
            step = jnp.einsum(
                "btd,hd->bth", a.astype(jnp.float32), self.param(
                    "dt_proj", nn.initializers.lecun_normal(in_axis=1,
                                                            out_axis=0),
                    (h, self.dim), jnp.float32), precision=HIGHEST)
            xbc = nn.silu(causal_conv(
                xbc.astype(jnp.float32), self.param(
                    "conv", nn.initializers.lecun_normal(),
                    (self.conv_kernel, xbc.shape[-1]), jnp.float32))
                + self.param("conv_bias", nn.initializers.zeros,
                             (xbc.shape[-1],), jnp.float32))
            x, bmat, cmat = jnp.split(xbc, [inner, inner + g * n], axis=-1)
            first = self.mamba_heads[0]
            a_log = self.param(
                "A_log", lambda _key, shape: jnp.log(
                    1.0 + first + jnp.arange(shape[0], dtype=jnp.float32)),
                (h,))
            skip = self.param("D", nn.initializers.ones, (h,))
            dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,))
            with jax.named_scope("ssd_scan"):
                y = ssd_chunked(
                    x.reshape(b, t, h, p), jax.nn.softplus(step + dt_bias),
                    -jnp.exp(a_log), bmat.reshape(b, t, g, n),
                    cmat.reshape(b, t, g, n), skip, self.chunk, dtype=dt)
            y = y.reshape(b, t, g, r * p) * nn.silu(
                z.astype(jnp.float32)).reshape(b, t, g, r * p)
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, -1, keepdims=True) + self.eps)
            y = y.reshape(b, t, inner) * self.param(
                "gated_norm", nn.initializers.ones, (inner,), jnp.float32)
            return dense(self.dim, name="out_proj")(y.astype(dt))

    def _attention(self, a):
        dt = self.compute_dtype
        b, t, _ = a.shape
        hd = self.head_dim
        hq, group = _held(self.query_heads, self.kv_heads, "attention")
        kv = hq // group
        dense = partial(nn.Dense, use_bias=False, dtype=dt)
        with jax.named_scope("attention"):
            q = dense(hq * hd, name="q_proj")(a).reshape(
                b, t, kv, group, hd)
            k, v = (dense(kv * hd, name=name)(a).reshape(b, t, kv, hd)
                    for name in ("k_proj", "v_proj"))
            o = causal_core(q)(q, k, v)
            return dense(self.dim, name="o_proj")(o.reshape(b, t, hq * hd))

    def _experts(self, m):
        dt = self.compute_dtype
        b, t, d = m.shape
        chosen, weights = biased_sigmoid_router(
            self, m, self.num_experts, self.experts_per_token,
            self.routed_scale)
        dense = partial(nn.Dense, use_bias=False, dtype=dt)
        with jax.named_scope("shared_expert"):
            y = dense(d, name="shared_down")(relu2(
                dense(self.shared_width, name="shared_up")(m)))
        held = self.local_experts[1] - self.local_experts[0]
        init = nn.initializers.lecun_normal()
        w_up = self.param("w_up", init, (held, d, self.expert_width),
                          jnp.float32).astype(dt)
        w_down = self.param("w_down", init, (held, self.expert_width, d),
                            jnp.float32).astype(dt)
        fill = _filled(self.expert_width)
        if fill:
            w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, fill)))
            w_down = jnp.pad(w_down, ((0, 0), (0, fill), (0, 0)))
        routed, group_sizes = sparse_dispatch(
            m.reshape(b * t, d).astype(dt), chosen.reshape(b * t, -1),
            weights.reshape(b * t, -1), plain_experts(w_up, w_down, relu2),
            self.local_experts, self.num_experts)
        return y.astype(jnp.float32) + routed.reshape(b, t, d).astype(
            jnp.float32), jnp.sum(group_sizes)

    @nn.compact
    def __call__(self, x):
        """``x`` [B, T, D] float32 -> (x', rows routed to the held
        experts: 0 in a layer that has none)."""
        rows = jnp.zeros((), jnp.int32)
        if self.kind == EXPERTS:
            # routing reads the norm's output in float32
            y, rows = self._experts(
                RMSNorm(self.eps, jnp.float32, name="norm")(x))
        else:
            a = RMSNorm(self.eps, self.compute_dtype, name="norm")(x)
            y = self._mamba(a) if self.kind == MAMBA else self._attention(a)
        return x + y.astype(jnp.float32), rows


class NemotronH(nn.Module):
    vocab: int
    dim: int
    pattern: str                    # a letter a layer: M, E, *
    mamba_head_dim: int
    state_size: int
    conv_kernel: int
    chunk: int
    mamba_heads: Tuple[int, int]
    mamba_groups: Tuple[int, int]
    head_dim: int
    query_heads: Tuple[int, int]
    kv_heads: Tuple[int, int]
    num_experts: int
    experts_per_token: int
    expert_width: int
    shared_width: int
    local_experts: Tuple[int, int]
    routed_scale: float
    eps: float = 1e-5
    compute_dtype: Any = jnp.float32

    def counts(self, batch: int, t: int, kernel: bool = False):
        """What a pass over ``batch`` sequences of ``t`` positions has
        by shape: (all routed (token, slot) rows; live and computed
        score entries of the attention layers' held query heads,
        ``kernel``: as the kernel computes them
        (``transformer.runs_kernel``); (token, head) pairs through the
        Mamba layers' scan; the dependent chunk steps that takes)."""
        layers = {kind: self.pattern.count(kind)
                  for kind in (MAMBA, EXPERTS, ATTENTION)}
        live, computed = score_entries(t)
        if kernel:
            computed = kernel_score_entries(t, self.head_dim)
        cores = batch * layers[ATTENTION] * (
            self.query_heads[1] - self.query_heads[0])
        held = self.mamba_heads[1] - self.mamba_heads[0]
        return (batch * t * layers[EXPERTS] * self.experts_per_token,
                cores * live, cores * computed,
                batch * t * held * layers[MAMBA],
                batch * layers[MAMBA] * chunks_of(t, self.chunk))

    @nn.compact
    def __call__(self, tokens, targets=None):
        """``tokens`` [B, T] -> (logits [B, T, vocab] float32, or with
        ``targets`` [B, T] their negative log-likelihoods [B, T]; rows
        routed to the held experts summed over the expert layers)."""
        if set(self.pattern) - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(f"pattern {self.pattern!r}: a layer is one "
                             f"of {MAMBA} {EXPERTS} {ATTENTION}")
        x = nn.Embed(self.vocab, self.dim, name="embed")(tokens)
        rows_local = 0
        block = nn.remat(NemotronHBlock)
        for i, kind in enumerate(self.pattern):
            x, rows = block(
                self.dim, kind, self.mamba_head_dim, self.state_size,
                self.conv_kernel, self.chunk, tuple(self.mamba_heads),
                tuple(self.mamba_groups), self.head_dim,
                tuple(self.query_heads), tuple(self.kv_heads),
                self.num_experts, self.experts_per_token,
                self.expert_width, self.shared_width,
                tuple(self.local_experts), self.routed_scale, self.eps,
                self.compute_dtype, name=f"block{i}")(x)
            rows_local = rows_local + rows

        def exit_of(_mdl, x, targets=None):
            x = RMSNorm(self.eps, self.compute_dtype, name="norm_f")(x)
            logits = nn.Dense(
                self.vocab, use_bias=False, dtype=self.compute_dtype,
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32),
                name="head")(x)
            if targets is None:
                return logits
            return -jnp.take_along_axis(jax.nn.log_softmax(logits),
                                        targets[..., None], axis=-1)[..., 0]

        with jax.named_scope("head"):
            if targets is None:
                return exit_of(self, x), rows_local
            # the loss's way: HEAD_ROWS positions at a time, each block's
            # logits computed again on the way back, so that a block's
            # [rows, vocab] float32 and not the sequence's are alive
            b, t, d = x.shape
            rows = min(HEAD_ROWS, t)
            blocks = -(-t // rows)
            pad = ((0, 0), (0, blocks * rows - t))

            def some_rows(mdl, carry, xs):
                return carry, nn.remat(exit_of)(mdl, *xs)

            _, nll = nn.scan(
                some_rows, variable_broadcast="params",
                split_rngs={"params": False})(self, None, (
                    jnp.pad(x, pad + ((0, 0),)).reshape(
                        b, blocks, rows, d).swapaxes(0, 1),
                    jnp.pad(targets, pad).reshape(
                        b, blocks, rows).swapaxes(0, 1)))
            return nll.swapaxes(0, 1).reshape(b, blocks * rows)[:, :t], \
                rows_local


def next_token_loss(model, variables, toks):
    """``toks`` [B, T+1]: the mean next-token cross-entropy of the
    rank's share, in ``moe.next_token_loss``'s form: (loss, [the rows
    routed to the held experts, then ``model.counts``]), the counts as
    float32. The model takes the targets and gives the positions'
    negative log-likelihoods, so no sequence's logits are held."""
    nll, rows_local = model.apply(variables, toks[:, :-1], toks[:, 1:])
    by_shape = model.counts(toks.shape[0], toks.shape[1] - 1,
                            runs_kernel(toks[:, :-1]))
    return jnp.mean(nll), jnp.stack([rows_local.astype(jnp.float32),
                                     *(jnp.float32(c) for c in by_shape)])
