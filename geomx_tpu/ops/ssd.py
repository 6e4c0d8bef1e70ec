"""The selective state-space recurrence of Mamba-2 (Dao & Gu 2024,
"Transformers are SSMs": state-space duality, SSD), in its chunked form,
with the token recurrence beside it as its oracle.

A head keeps a state ``h`` [P, N], zero before the first token. Token t
decays it by a scalar of its own, writes the outer product of its input
and the token's ``B`` into it, and reads it out under the token's ``C``:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t
    y_t = h_t C_t + D x_t

``dt_t > 0`` a head and token (the caller's softplus), ``A < 0`` and the
skip ``D`` a head, ``x_t`` [P] a head, ``B_t`` and ``C_t`` [N] a GROUP of
heads: the H heads are G groups of H / G neighbours, and a head reads its
group's.

:func:`ssd_chunked` computes the same in chunks of ``chunk`` tokens. With
``a = dt A``, ``c`` its running sum inside a chunk and ``L_ij = exp(c_i -
c_j)`` for i >= j (0 above the diagonal; every exponent is <= 0, so no
decay is ever inverted):

    inside a chunk   y_i  = sum_{j <= i} (C_i . B_j) L_ij dt_j x_j
    a chunk's write  S    = sum_j exp(c_last - c_j) dt_j x_j (x) B_j
    chunk after chunk, h the state the chunk found:
                     y_i += exp(c_i) h C_i
                     h    = exp(c_last) h + S

Everything but the last line is matmuls over all chunks at once (the
``C B^T`` product once a group, not once a head); the chain of states is
one ``lax.scan`` over the chunks whose step is a multiply-add on [P, N]
a head. The states are kept a CHUNK apart (T / chunk of them), never a
token apart, and the way back is JAX's own of this program. A length
that is no multiple of ``chunk`` is padded with tokens of ``dt = 0``,
which neither decay nor write, and their outputs are dropped.

Precision: ``dt``, ``A``, the running sums and every exponential are
float32, and so are the states along the chain; the four products take
their operands in ``dtype`` and accumulate in float32 (``highest`` where
``dtype`` is float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["CHUNK", "chunks_of", "ssd_chunked", "ssd_recurrent"]

CHUNK = 128


def chunks_of(t: int, chunk: int = CHUNK) -> int:
    """Dependent steps of the chain of states over ``t`` tokens."""
    return -(-t // chunk)


def _grouped(x, groups: int):
    """[..., H, P] -> [..., G, H / G, P]: heads by the group they read."""
    return x.reshape(x.shape[:-2] + (groups, x.shape[-2] // groups,
                                     x.shape[-1]))


def ssd_recurrent(x, dt, A, B, C, D):
    """The recurrence a token at a time, float32 at ``highest``: ``x``
    [Bt, T, H, P], ``dt`` [Bt, T, H], ``A``, ``D`` [H], ``B``, ``C``
    [Bt, T, G, N] -> ``y`` [Bt, T, H, P]. The tests' oracle."""
    bt, _t, h, p = x.shape
    g, n = B.shape[-2:]
    f32 = jnp.float32
    x, dt, B, C = (v.astype(f32) for v in (x, dt, B, C))
    of = jnp.arange(h) // (h // g)      # a head's group

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        decay = jnp.exp(dt_t * A)[..., None, None]
        state = decay * state + jnp.einsum(
            "bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t[:, of],
            precision="highest")
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t[:, of],
                                 precision="highest")

    _, y = jax.lax.scan(
        token, jnp.zeros((bt, h, p, n), f32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1) + D[:, None] * x


def ssd_chunked(x, dt, A, B, C, D, chunk: int = CHUNK, dtype=None):
    """The same recurrence in chunks (the module's equations); shapes as
    :func:`ssd_recurrent`, ``dtype`` the matmuls' operand type (``x``'s
    by default). Returns ``y`` [Bt, T, H, P] float32."""
    bt, t, h, p = x.shape
    g, n = B.shape[-2:]
    f32 = jnp.float32
    md = jnp.dtype(dtype or x.dtype)
    precision = "highest" if md == f32 else None
    nc = chunks_of(t, chunk)
    pad = nc * chunk - t

    def chunked(v):
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return v.reshape((bt, nc, chunk) + v.shape[2:])

    def mm(eq, u, v):
        return jnp.einsum(eq, u.astype(md), v.astype(md),
                          precision=precision, preferred_element_type=f32)

    xc, dtc, bc, cc = (chunked(v) for v in (x, dt.astype(f32), B, C))
    # c: the running sum of dt A inside a chunk, [Bt, nc, L, H]
    c = jnp.cumsum(dtc * A.astype(f32), axis=2)
    live = jnp.tril(jnp.ones((chunk, chunk), bool))
    # L_ij = exp(c_i - c_j), i >= j: [Bt, nc, H, L, L]; the exponent
    # above the diagonal is positive and unbounded: masked BEFORE exp
    by_head = jnp.moveaxis(c, 2, -1)
    decays = jnp.exp(jnp.where(
        live, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    written = xc.astype(f32) * dtc[..., None]           # dt_j x_j
    # inside a chunk: one C B^T a group, times each head's decays
    scores = mm("bcigs,bcjgs->bcgij", cc, bc)
    weights = decays.reshape(bt, nc, g, h // g, chunk, chunk) \
        * scores[:, :, :, None]
    y = mm("bcgrij,bcjgrp->bcigrp", weights, _grouped(written, g))
    # a chunk's write to the state it leaves behind: [Bt, nc, G, r, P, N]
    to_end = jnp.exp(c[:, :, -1:] - c)
    wrote = mm("bcjgrp,bcjgs->bcgrps",
               _grouped(written * to_end[..., None], g), bc)
    through = _grouped(jnp.exp(c[:, :, -1])[..., None], g)[..., None]

    def chain(state, step):
        wrote_n, through_n = step
        return through_n * state + wrote_n, state

    _, found = jax.lax.scan(
        chain, jnp.zeros((bt, g, h // g, p, n), f32),
        (jnp.moveaxis(wrote, 1, 0), jnp.moveaxis(through, 1, 0)))
    # what the state a chunk found gives its tokens
    y = y + mm("bcigs,bcgrps->bcigrp", cc, jnp.moveaxis(found, 0, 1)) \
        * _grouped(jnp.exp(c)[..., None], g)
    y = y.reshape(bt, nc * chunk, h, p)[:, :t]
    return y + D.astype(f32)[:, None] * x.astype(f32)
