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

Everything before the chain ``S -> v' -> S`` is matmuls over all chunks
at once. The two dependent chains, the solve's rows and the chunks'
states, run in one of two forms, and :func:`runs_kernel` says which:

- the ``lax.scan`` form (the CPU suite, a mesh, head sizes off 128,
  another chunk than :data:`CHUNK`; with the token recurrence the
  tests' oracle): XLA's ``triangular_solve`` a chunk, then a scan over
  the chunks that carries the state and computes ``o`` as it goes; the
  backward pass is JAX's own of that program.
- the kernel form (a TPU backend): the solve without XLA's row-by-row
  ``while`` over the chunk (:func:`_unit_lower_solve`: the rows of
  16-row diagonal blocks one 16-step loop, every block of every chunk
  at once with the matrices along the minor axis, the blocks joined by
  float32 products, the cotangents the solve's own), and the chain as a
  pair of Pallas kernels under a ``jax.custom_vjp`` (:func:`_chain`): a
  grid of (batch, head blocks, chunks), the chunks the sequential axis,
  a head's state (backward: its cotangent) in float32 VMEM scratch from
  the first chunk to the last, so a step's four products (backward:
  eight) run on tiles that are already on the chip, and neither the
  states nor their cotangents pass through a loop of XLA's. The forward
  kernel writes ``o`` and, for the way back, the state every chunk
  found and ``v'``.

Either way the states are kept a CHUNK apart (T / chunk of them), never
a token apart.

What the way back reads of the two chains carries a name
(``jax.ad_checkpoint.checkpoint_name``): :data:`SOLVED` the solve's
``u`` and ``w`` (kernel form: its inverse too, all its cotangents
take), :data:`CHAINED` the states the chain's kernel found and
``v'``. A caller that recomputes the rule on the way back
(``jax.checkpoint``) and gives it the policy :data:`keeps` runs neither
kernel-form chain a second time: the chunk-parallel part is computed
again, the chain's operands with it, and the named values are the link
between the two. At 4,096 tokens and 16 value heads of 128 x 128 they
are 0.17 GB a layer and pass (the inverse 17 MB, ``u`` and ``w`` 67,
the states 67, ``v'`` 17 in bfloat16). The scan form names its solve's
result alike, so the CPU suite differentiates under the same policy;
its chains are JAX's own programs and run again all the same
(``triangular_solve``'s rule reads the primitive's result, not the
named copy; the scan keeps its residuals to itself). Without a policy
the names are inert.

Precision: the decays, their sums and exponentials, the solve and the
state (and its cotangent) are float32; the matmuls take their operands
in ``dtype`` and accumulate in float32 (``highest`` where ``dtype`` is
float32), in both forms.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

__all__ = ["gated_delta_rule", "gated_delta_rule_recurrent", "chunks_of",
           "runs_kernel", "keeps"]

CHUNK = 64
SOLVE_BLOCK = 16        # rows of a diagonal block: the solve's one loop
HEADS_A_STEP = 8        # value heads a grid step of the chain, at most
# ``checkpoint_name``s of what the way back reads of the two chains and
# the policy that keeps them (the module's docstring). ONE policy
# object: JAX caches how a ``jit`` inside a checkpoint splits into kept
# and recomputed by the policy's identity, and a policy made a layer
# lowers the kernel form once a layer (nine Mosaic calls in the cell's
# ``grad_step`` where this gives five)
SOLVED = "gated_delta_solved"   # u, w; kernel form: the inverse too
CHAINED = "gated_delta_chained"  # kernel form: the states found, v'
keeps = jax.checkpoint_policies.save_only_these_names(SOLVED, CHAINED)


def chunks_of(t: int, chunk: int = CHUNK) -> int:
    """Dependent steps :func:`gated_delta_rule` makes over ``t`` tokens."""
    return -(-t // chunk)


def runs_kernel(q, v, chunk: int, forced: Optional[bool] = None) -> bool:
    """THE rule for the form of :func:`gated_delta_rule`'s two dependent
    chains over ``q`` [B, T, H, dk] and ``v`` [B, T, H, dv]: the kernel
    form where Pallas compiles (``ops.pallas_interpret()`` false: a TPU
    backend), no mesh is in play (a Pallas call has no partitioning
    rule; seen as ``models.transformer.runs_kernel`` sees it: the
    abstract mesh of the context and of the operand's own sharding),
    ``dk`` and ``dv`` are whole lane tiles (multiples of 128) and the
    chunk is :data:`CHUNK`; the ``lax.scan`` form otherwise. One
    algorithm that wants another form on another backend: the rule
    reads what the trace can see and nothing names a model. ``forced``
    is for tests: the answer itself."""
    if forced is not None:
        return forced
    from geomx_tpu.ops import pallas_interpret

    return (not pallas_interpret()
            and jax.sharding.get_abstract_mesh().empty
            and jax.typeof(q).sharding.mesh.empty
            and q.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0
            and chunk == CHUNK)


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


def _diagonal_blocks(a, count: int):
    """The ``count`` (a power of two) diagonal blocks of ``a`` [n, n,
    matrix] side by side along the minor axis, [n / count, n / count,
    count * matrix], in BIT-REVERSED order (0, 2, 1, 3 of four): the
    even blocks are the first half of the lanes and the odd ones the
    second, and so again among the blocks a join leaves, so a pair's
    members are two plain slices of whole lanes at every level. One
    masked sum over the block columns, whatever ``count``."""
    n, _, m = a.shape
    size = n // count
    bits = count.bit_length() - 1
    on = jnp.eye(count, dtype=bool)[:, None, :, None, None]
    blocks = jnp.sum(jnp.where(on, a.reshape(count, size, count, size, m),
                               0.0), axis=2)
    blocks = blocks.reshape((2,) * bits + (size, size, m)).transpose(
        bits, bits + 1, *reversed(range(bits)), bits + 2)
    return blocks.reshape(size, size, count * m)


def _product(x, y):
    """[i, j, matrix] x [j, k, matrix] -> [i, k, matrix]: one broadcast
    product, reduced over j (elementwise float32 on whole lanes)."""
    return jnp.sum(x[:, :, None] * y[None], axis=1)


@jax.jit
def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower ``a`` [..., n, n] float32 as
    elementwise code with every matrix along the MINOR axis ([row,
    column, matrix]), so a step is whole lanes whatever ``n``. Row i of
    the inverse is ``e_i - sum_j a_ij row_j``: forward substitution,
    one ``fori_loop`` over the :data:`SOLVE_BLOCK` rows of a diagonal
    block, run on every block of every matrix at once; the sum is over
    ALL j, since the rows not yet written are zero. Then blocks become
    one two at a time by the exact formula ``[[X, 0], [B, Y]]^-1 =
    [[X^-1, 0], [-Y^-1 B X^-1, Y^-1]]``, the two small products a
    broadcast product and a reduction each, in float32. No power series:
    where neighbouring keys align and beta nears 1, ``a`` nears the
    all-ones strict triangle, whose powers reach binomial size before
    they cancel. ``n`` that is no power-of-two multiple of the block is
    one block of n rows. ROLLED: some 110 equations whatever the block
    and the chunk (``tests/test_qwen3_next.py`` holds it under 200): the
    rows written out as straight-line code are 2,000, and nine inlined
    copies of them doubled the cell's ``grad_step`` and its compile
    (PERF.md section 6, PR 47 and PR 48). Under its own ``jit``, so
    every pass over the caller's program reads one equation."""
    lead, n = a.shape[:-2], a.shape[-1]
    blocks = n // SOLVE_BLOCK
    if n % SOLVE_BLOCK or blocks & (blocks - 1):
        blocks = 1
    size = n // blocks
    a = jnp.moveaxis(a.reshape((-1, n, n)), 0, -1)
    m = a.shape[-1]
    diag = _diagonal_blocks(a, blocks)       # [row, column, block x matrix]
    eye = jnp.eye(size, dtype=a.dtype)

    def row(i, rows):
        d = jax.lax.dynamic_index_in_dim(diag, i, 0, keepdims=False)
        e = jax.lax.dynamic_index_in_dim(eye, i, 0, keepdims=False)
        new = e[:, None] - jnp.sum(d[:, None] * rows, axis=0)
        return jax.lax.dynamic_update_index_in_dim(rows, new, i, 0)

    inverse = jax.lax.fori_loop(0, size, row, jnp.zeros_like(diag))
    while blocks > 1:
        blocks //= 2
        x, y = inverse[..., :blocks * m], inverse[..., blocks * m:]
        below = _diagonal_blocks(a, blocks)[size:, :size]
        corner = -_product(_product(y, below), x)
        inverse = jnp.concatenate([
            jnp.concatenate([x, jnp.zeros_like(x)], 1),
            jnp.concatenate([corner, y], 1)], 0)
        size *= 2
    return jnp.moveaxis(inverse, -1, 0).reshape(lead + (n, n))


@jax.custom_vjp
def _unit_lower_solve(a, *sides):
    """``(I + a)^-1 x`` for every ``x`` [..., n, d] of ``sides``, ``a``
    strictly lower [..., n, n], all float32: the inverse
    (:func:`_unit_lower_inverse`) times the sides at ``highest``. The
    cotangents are the solve's own, ``dx = (I + a)^-T dy`` and ``da =
    -strict_lower(sum dx y^T)``: two products a side with the inverse
    the forward made, nothing differentiated through its steps."""
    return _unit_lower_solve_fwd(a, *sides)[0]


@jax.named_scope("unit_lower_solve")
def _unit_lower_solve_fwd(a, *sides):
    inverse = checkpoint_name(_unit_lower_inverse(a), SOLVED)
    out = tuple(checkpoint_name(jnp.einsum(
        "...ij,...jd->...id", inverse, x,
        precision=jax.lax.Precision.HIGHEST), SOLVED) for x in sides)
    return out, (inverse, out)


@jax.named_scope("unit_lower_solve")
def _unit_lower_solve_bwd(res, cotangents):
    hp = jax.lax.Precision.HIGHEST
    inverse, out = res
    d_sides = tuple(jnp.einsum("...ji,...jd->...id", inverse, dy,
                               precision=hp) for dy in cotangents)
    n = inverse.shape[-1]
    d_a = -sum(jnp.einsum("...id,...jd->...ij", dx, y, precision=hp)
               for dx, y in zip(d_sides, out))
    return (jnp.where(jnp.tril(jnp.ones((n, n), bool), -1), d_a, 0.0),
            *d_sides)


_unit_lower_solve.defvjp(_unit_lower_solve_fwd, _unit_lower_solve_bwd)


def _heads_a_step(h: int) -> int:
    """Value heads a grid step of the chain works on: the largest
    divisor of ``h`` up to :data:`HEADS_A_STEP`. A step's tiles are a
    few hundred KB a head, so eight heads keep the pipeline's two
    buffers a few MB and a step's DMA far over the grid's own cost."""
    return max(d for d in range(1, min(h, HEADS_A_STEP) + 1) if h % d == 0)


@functools.lru_cache(maxsize=None)
def _chain_calls(b: int, h: int, n: int, chunk: int, dk: int, dv: int,
                 dtype: str, interpret: bool):
    """(forward, backward) pallas_calls of :func:`_chain` for one static
    shape: operands [B, H, N, ...], grid (B, H / heads a step, N) with
    the chunks innermost and sequential; the state (backward: its
    cotangent) of the step's heads is float32 VMEM scratch that lives
    from the first chunk to the last. The forward call also returns
    what the backward one reads: (o, S_n found, v', last state)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dt = jnp.dtype(dtype)
    f32 = jnp.float32
    precision = jax.lax.Precision.HIGHEST if dt == f32 else None
    hb = _heads_a_step(h)
    grid = (b, h // hb, n)

    def dot(x, y, contract):
        return jax.lax.dot_general(
            x, y, ((contract[:1], contract[1:]), ((), ())),
            precision=precision, preferred_element_type=f32)

    params = {} if interpret else dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024))

    def per_chunk(rows: int, cols: int, back: bool = False):
        """[B, H, N, rows, cols]: the step's heads, one chunk (the
        backward walks from the last)."""
        return pl.BlockSpec(
            (1, hb, 1, rows, cols),
            (lambda i, j, c: (i, j, n - 1 - c, 0, 0)) if back
            else (lambda i, j, c: (i, j, c, 0, 0)))

    whole = pl.BlockSpec((1, hb, dk, dv), lambda i, j, c: (i, j, 0, 0))

    def fwd_kernel(u_ref, w_ref, k_ref, through_ref, q_ref, scores_ref,
                   o_ref, found_ref, v_new_ref, last_ref, s_ref):
        c = pl.program_id(2)

        @pl.when(c == 0)
        def _():
            s_ref[:] = jnp.zeros_like(s_ref)

        for j in range(hb):
            s = s_ref[j]
            found_ref[0, j, 0] = s
            s_op = s.astype(dt)
            v_new = (u_ref[0, j, 0] - dot(w_ref[0, j, 0], s_op,
                                          (1, 0))).astype(dt)
            v_new_ref[0, j, 0] = v_new
            o_ref[0, j, 0] = dot(q_ref[0, j, 0], s_op, (1, 0)) + dot(
                scores_ref[0, j, 0], v_new, (1, 0))
            s_ref[j] = through_ref[0, j, 0] * s + dot(
                k_ref[0, j, 0], v_new, (0, 0))

        @pl.when(c == n - 1)
        def _():
            last_ref[0] = s_ref[:]

    def fwd(u, w, k_to_end, through, q_decayed, scores):
        return pl.pallas_call(
            fwd_kernel, grid=grid,
            in_specs=[per_chunk(chunk, dv), per_chunk(chunk, dk),
                      per_chunk(chunk, dk), per_chunk(1, dv),
                      per_chunk(chunk, dk), per_chunk(chunk, chunk)],
            out_specs=[per_chunk(chunk, dv), per_chunk(dk, dv),
                       per_chunk(chunk, dv), whole],
            out_shape=[jax.ShapeDtypeStruct((b, h, n, chunk, dv), f32),
                       jax.ShapeDtypeStruct((b, h, n, dk, dv), f32),
                       jax.ShapeDtypeStruct((b, h, n, chunk, dv), dt),
                       jax.ShapeDtypeStruct((b, h, dk, dv), f32)],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
            interpret=interpret, **params,
        )(u, w, k_to_end, through, q_decayed, scores)

    def bwd_kernel(w_ref, k_ref, through_ref, q_ref, scores_ref, found_ref,
                   v_new_ref, d_o_ref, d_last_ref,
                   du_ref, dw_ref, dk_ref, d_through_ref, dq_ref,
                   d_scores_ref, ds_ref):
        c = pl.program_id(2)

        @pl.when(c == 0)
        def _():
            ds_ref[:] = d_last_ref[0]

        for j in range(hb):
            ds = ds_ref[j]          # to the state this chunk LEFT
            ds_op = ds.astype(dt)
            found = found_ref[0, j, 0]
            found_op = found.astype(dt)
            v_new = v_new_ref[0, j, 0]
            d_o = d_o_ref[0, j, 0].astype(dt)
            dv_new = dot(scores_ref[0, j, 0], d_o, (0, 0)) + dot(
                k_ref[0, j, 0], ds_op, (1, 0))
            du_ref[0, j, 0] = dv_new
            dv_op = dv_new.astype(dt)
            d_scores_ref[0, j, 0] = dot(d_o, v_new, (1, 1)).astype(dt)
            dq_ref[0, j, 0] = dot(d_o, found_op, (1, 1)).astype(dt)
            dk_ref[0, j, 0] = dot(v_new, ds_op, (1, 1)).astype(dt)
            dw_ref[0, j, 0] = (-dot(dv_op, found_op, (1, 1))).astype(dt)
            d_through_ref[0, j, 0] = jnp.sum(ds * found, axis=0,
                                             keepdims=True)
            ds_ref[j] = (through_ref[0, j, 0] * ds
                         + dot(q_ref[0, j, 0], d_o, (0, 0))
                         - dot(w_ref[0, j, 0], dv_op, (0, 0)))

    def bwd(w, k_to_end, through, q_decayed, scores, found, v_new, d_o,
            d_last):
        back = functools.partial(per_chunk, back=True)
        return pl.pallas_call(
            bwd_kernel, grid=grid,
            in_specs=[back(chunk, dk), back(chunk, dk), back(1, dv),
                      back(chunk, dk), back(chunk, chunk), back(dk, dv),
                      back(chunk, dv), back(chunk, dv), whole],
            out_specs=[back(chunk, dv), back(chunk, dk), back(chunk, dk),
                       back(1, dv), back(chunk, dk), back(chunk, chunk)],
            out_shape=[jax.ShapeDtypeStruct((b, h, n, chunk, dv), f32),
                       jax.ShapeDtypeStruct((b, h, n, chunk, dk), dt),
                       jax.ShapeDtypeStruct((b, h, n, chunk, dk), dt),
                       jax.ShapeDtypeStruct((b, h, n, 1, dv), f32),
                       jax.ShapeDtypeStruct((b, h, n, chunk, dk), dt),
                       jax.ShapeDtypeStruct((b, h, n, chunk, chunk), dt)],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
            interpret=interpret, **params,
        )(w, k_to_end, through, q_decayed, scores, found, v_new, d_o,
          d_last)

    return fwd, bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chain(interpret, u, w, k_to_end, through, q_decayed, scores):
    """The chunks' dependent chain as kernels (``interpret``: Pallas's
    interpret mode, as ``ops.pallas_interpret()`` said: static, so a
    trace is kept under what it holds). ``u`` [B, H, N, chunk, dv]
    float32; ``w``, ``k_to_end``, ``q_decayed`` [B, H, N, chunk, dk]
    and ``scores`` [B, H, N, chunk, chunk] in the matmuls' type;
    ``through`` [B, H, N, 1, dv] float32 (a chunk's decay along a row).
    From ``S_0 = 0``, chunk after chunk:

        v'_n = u_n - w_n S_n;   o_n = q_decayed_n S_n + scores_n v'_n
        S_{n+1} = through_n S_n + k_to_end_n^T v'_n

    Returns (``o`` [B, H, N, chunk, dv] float32, the state after the
    last chunk [B, H, dk, dv]); kept for the way back are the state
    every chunk FOUND (``S_n``, float32) and ``v'`` in the matmuls'
    type, which is all its readers take it in. Backward, from the last
    chunk, ``dS`` the cotangent of the state a chunk left:

        dv'  = scores_n^T do_n + k_to_end_n dS;      du_n = dv'
        dscores_n = do_n v'_n^T;  dq_decayed_n = do_n S_n^T
        dk_to_end_n = v'_n dS^T;  dw_n = -dv' S_n^T;  dthrough_n = <dS, S_n>
        dS   = through_n dS + q_decayed_n^T do_n - w_n^T dv'
    """
    return _chain_fwd(interpret, u, w, k_to_end, through, q_decayed,
                      scores)[0]


def _calls_for(interpret, u, w):
    b, h, n, chunk, dv = u.shape
    return _chain_calls(b, h, n, chunk, w.shape[-1], dv, w.dtype.name,
                        interpret)


def _chain_fwd(interpret, u, w, k_to_end, through, q_decayed, scores):
    o, found, v_new, last = _calls_for(interpret, u, w)[0](
        u, w, k_to_end, through, q_decayed, scores)
    return (o, last), (w, k_to_end, through, q_decayed, scores,
                       checkpoint_name(found, CHAINED),
                       checkpoint_name(v_new, CHAINED))


def _chain_bwd(interpret, res, cotangents):
    # (du, dw, dk_to_end, dthrough, dq_decayed, dscores); v' has u's shape
    return tuple(_calls_for(interpret, res[-1], res[0])[1](
        *res, *cotangents))


_chain.defvjp(_chain_fwd, _chain_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK, dtype=None):
    """The chunked form; arguments and results as
    :func:`gated_delta_rule_recurrent`, ``o`` in float32. T need not be
    a multiple of ``chunk``: the tail is padded with tokens that neither
    decay nor write. The solve and the chain run in the form
    :func:`runs_kernel` gives; the kernel form under a ``jit`` of its
    own, so that a model's layers, its ``checkpoint``, linearisation
    and transpose, and a process's trainers trace, differentiate and
    lower it once a shape and not once each."""
    from geomx_tpu.ops import pallas_interpret

    kernel = runs_kernel(q, v, chunk)
    return (_chunked_under_jit if kernel else _chunked)(
        q, k, v, g, beta, chunk, jnp.dtype(dtype or q.dtype), kernel,
        pallas_interpret())


def _chunked(q, k, v, g, beta, chunk: int, dt, kernel: bool,
             interpret: bool):
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
    sides = v * beta[..., None], k_beta * jnp.exp(c)[..., None]
    if kernel:
        u, w = _unit_lower_solve(a, *sides)
    else:
        uw = checkpoint_name(jax.lax.linalg.triangular_solve(
            a, jnp.concatenate(sides, -1),
            left_side=True, lower=True, unit_diagonal=True), SOLVED)
        u, w = uw[..., :dv], uw[..., dv:]
    scores = jnp.where(lower, mm("bhnik,bhnjk->bhnij", q, k) * decay, 0.0)
    q_decayed = q * jnp.exp(c)[..., None]
    k_to_end = k * jnp.exp(c[..., -1:] - c)[..., None]
    through = jnp.exp(c[..., -1])
    if kernel:
        o, state = _chain(
            interpret, u, w.astype(dt), k_to_end.astype(dt),
            jnp.broadcast_to(through[..., None, None], (b, h, n, 1, dv)),
            q_decayed.astype(dt), scores.astype(dt))
        # [B, H, N, chunk, dv] -> [B, T, H, dv]
        o = o.transpose(0, 2, 3, 1, 4)
        return o.reshape(b, n * chunk, h, dv)[:, :t], state
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


_chunked_under_jit = jax.jit(_chunked, static_argnums=(5, 6, 7, 8))
