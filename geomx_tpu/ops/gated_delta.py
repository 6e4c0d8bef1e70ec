"""The gated delta rule: a linear-attention layer's recurrence over the
sequence (Gated DeltaNet; Yang et al. 2024, "Gated Delta Networks"), in
its chunked form, with the token recurrence beside it as its oracle.

A value head keeps a state ``S`` [dk, dv], zero before the first token.
Token t decays it, reads what it holds under the token's key, writes
the difference to the token's value back, and answers the query:

    S = exp(g_t) S;  r = v_t - S^T k_t;  S = S + k_t (beta_t r)^T
    o_t = S^T q_t

``g <= 0`` is the log of the decay, ``beta`` in (0, 1) the write
strength; q and k come as the caller normalised and scaled them.

:func:`gated_delta_rule` computes the same in chunks of ``chunk``
tokens. Inside a chunk, with ``c`` the running sum of ``g`` and
``D_ij = exp(c_i - c_j) = exp(g_{j+1} + ... + g_i)`` for i >= j:

    A = strict_lower((k beta) k^T * D)
    [u, w] = (I + A)^-1 [v beta, k beta exp(c)]    forward substitution
and then chunk after chunk, S the state the chunk found:
    v' = u - w S
    o  = (q exp(c)) S + lower(q k^T * D) v'
    S  = exp(c_last) S + (k exp(c_last - c))^T v'

Everything before the loop is matmuls over all chunks at once and a
unit-triangular solve a chunk; the loop carries one [dk, dv] state a
head through ``T / chunk`` dependent steps. The backward pass is JAX's
own of this program: the loop's states are kept a CHUNK apart (T /
chunk of them), never a token apart.

Precision: the decays, their sums and exponentials, the solve and the
state are float32; the matmuls take their operands in ``dtype`` and
accumulate in float32 (``highest`` where ``dtype`` is float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["gated_delta_rule", "gated_delta_rule_recurrent", "chunks_of"]

CHUNK = 64


def chunks_of(t: int, chunk: int = CHUNK) -> int:
    """Dependent steps :func:`gated_delta_rule` makes over ``t`` tokens."""
    return -(-t // chunk)


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The token recurrence, a ``lax.scan`` over T in float32 at
    ``highest``: ``q``, ``k`` [B, T, H, dk], ``v`` [B, T, H, dv], ``g``
    and ``beta`` [B, T, H]. Returns (o [B, T, H, dv], the state after
    the last token [B, H, dk, dv])."""
    hp = jax.lax.Precision.HIGHEST
    b, _t, h, dk = q.shape
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)

    def token(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = jnp.exp(g_t)[..., None, None] * s
        r = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=hp)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t, beta_t[..., None] * r,
                           precision=hp)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=hp)

    xs = [jnp.moveaxis(x.astype(jnp.float32), 1, 0)
          for x in (q, k, v, g, beta)]
    state, o = jax.lax.scan(token, state, xs)
    return jnp.moveaxis(o, 0, 1), state


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK, dtype=None):
    """The chunked form; arguments and results as
    :func:`gated_delta_rule_recurrent`, ``o`` in float32. T need not be
    a multiple of ``chunk``: the tail is padded with tokens that neither
    decay nor write."""
    dt = jnp.dtype(dtype or q.dtype)
    precision = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = chunks_of(t, chunk)

    def mm(eq, x, y):
        return jnp.einsum(eq, x.astype(dt), y.astype(dt),
                          precision=precision,
                          preferred_element_type=jnp.float32)

    def chunked(x):
        """[B, T, H, ...] float32 -> [B, H, N, chunk, ...]"""
        x = x.astype(jnp.float32)
        x = jnp.pad(x, ((0, 0), (0, n * chunk - t)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((b, n, chunk) + x.shape[2:]), 3, 1)

    q, k, v, g, beta = (chunked(x) for x in (q, k, v, g, beta))
    c = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # D_ij = exp(g_{j+1} + ... + g_i), summed as it stands: c_i - c_j
    # would lose the small sum between two large ones
    between = jnp.cumsum(jnp.where(
        jnp.tril(lower, -1), g[..., :, None], 0.0), axis=-2)
    decay = jnp.exp(jnp.where(lower, between, -jnp.inf))
    k_beta = k * beta[..., None]
    a = jnp.where(jnp.tril(lower, -1),
                  mm("bhnik,bhnjk->bhnij", k_beta, k) * decay, 0.0)
    uw = jax.lax.linalg.triangular_solve(
        a, jnp.concatenate(
            [v * beta[..., None], k_beta * jnp.exp(c)[..., None]], -1),
        left_side=True, lower=True, unit_diagonal=True)
    u, w = uw[..., :dv], uw[..., dv:]
    scores = jnp.where(lower, mm("bhnik,bhnjk->bhnij", q, k) * decay, 0.0)
    q_decayed = q * jnp.exp(c)[..., None]
    k_to_end = k * jnp.exp(c[..., -1:] - c)[..., None]
    through = jnp.exp(c[..., -1])
    state = jnp.zeros((b, h, dk, dv), jnp.float32)

    def one_chunk(s, x):
        u_n, w_n, q_n, scores_n, k_n, through_n = x
        v_new = u_n - mm("bhck,bhkv->bhcv", w_n, s)
        o = mm("bhck,bhkv->bhcv", q_n, s) + mm("bhij,bhjv->bhiv", scores_n,
                                               v_new)
        s = through_n[..., None, None] * s + mm("bhck,bhcv->bhkv", k_n,
                                                v_new)
        return s, o

    state, o = jax.lax.scan(
        one_chunk, state, [jnp.moveaxis(x, 2, 0) for x in (
            u, w, q_decayed, scores, k_to_end, through)])
    # [N, B, H, chunk, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4)
    return o.reshape(b, n * chunk, h, dv)[:, :t], state
