"""FlashAttention-2 as Pallas TPU kernels (forward + backward).

The reference has no attention operator at all (SURVEY.md §5.7 — its op
set predates attention-era models); this is part of the long-context
capability the TPU build adds as first-class. The kernel keeps both the
O(T^2) score matrix AND full-sequence K/V residency out of on-chip
memory: the key/value blocks ride the innermost grid dimension, so each
program instance holds one (block_q, D) query tile, one (block_k, D)
key/value tile, and fp32 VMEM scratch accumulators carrying the
online-softmax running (max, sumexp) state of FlashAttention-2 across
grid steps. Peak VMEM is O(block^2), independent of sequence length.
The backward recomputes probabilities blockwise from the saved
logsumexp (no quadratic residual): one kernel produces dQ (accumulating
over k-blocks) and one produces dK/dV (accumulating over q-blocks).

Layout contract matches ``geomx_tpu.models.transformer.dense_attention``:
``q, k, v`` are ``[B, T, H, D]`` and the return is ``[B, T, H, D]``.
Sequence lengths that are not multiples of the block size are
zero-padded; padded keys are masked out of the softmax and padded query
rows are sliced off (their cotangents are zero in the backward pass, so
they contribute nothing to dK/dV).

The logsumexp rides through the kernels as ``[B, H, T, 1]`` — TPU block
shapes must keep their last two dims (8, 128)-aligned or equal to the
full array dims, which a trailing singleton satisfies for vectors.

Interpret mode is chosen by ``ops.pallas_interpret()`` alone: compiled on
a TPU backend, interpreted elsewhere — which is what the CPU test suite
exercises against the dense reference.
"""

from __future__ import annotations

import functools

__all__ = ["flash_attention", "make_sharded_flash_attention"]


@functools.lru_cache(maxsize=None)
def _kernels(Tq: int, Tk: int, D: int, block_q: int, block_k: int,
             causal: bool, q_len: int, kv_len: int, interpret: bool):
    """Build (fwd, bwd_dq, bwd_dkv) pallas_calls for one static shape.

    All three work on ``[B, H, T, D]``-transposed arrays. Grids are
    (batch, head, outer-block, inner-block) with the inner dimension
    iterated sequentially on-core, accumulating into VMEM scratch.
    ``q_len`` <= Tq and ``kv_len`` <= Tk are the true (unpadded)
    lengths; keys past ``kv_len`` are masked out.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale = 1.0 / (D ** 0.5)
    nq = Tq // block_q
    nk = Tk // block_k
    neg_inf = -1e30

    # decode convention: when Tq != Tk the queries are the LAST q_len
    # positions of the key sequence (kv-cache decode), so q row i sits at
    # absolute position i + (kv_len - q_len)
    causal_offset = kv_len - q_len

    def _mask(qi, kj):
        """[block_q, block_k] validity mask for q-block qi, k-block kj."""
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        m = kpos < kv_len
        if causal:
            m = m & (qpos + causal_offset >= kpos)
        return m

    def _live(qi, kj):
        """Does (q-block qi, k-block kj) contribute at all?"""
        if not causal:
            return True
        return kj * block_k < (qi + 1) * block_q + causal_offset

    # -- forward ---------------------------------------------------------
    # grid (B, H, nq, nk): k-blocks innermost; acc/m/l scratch persists
    # across the k sweep for one q-block, finalized at the last k step.

    def fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                   acc_ref, m_ref, l_ref):
        qi, kj = pl.program_id(2), pl.program_id(3)

        @pl.when(kj == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, neg_inf)
            l_ref[:] = jnp.zeros_like(l_ref)

        @pl.when(_live(qi, kj))
        def _():
            # matmul operands stay in the INPUT dtype (bf16 runs the MXU
            # at full rate; an up-front f32 cast would halve it) with
            # f32 accumulation; softmax math is f32
            q = q_ref[0, 0]
            kb = k_ref[0, 0]
            vb = v_ref[0, 0]
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(_mask(qi, kj), s, neg_inf)
            m = m_ref[:, 0]
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m - m_new)
            l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
            acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:, 0] = m_new

        @pl.when(kj == nk - 1)
        def _():
            l = l_ref[:, 0]
            # rows with no valid key (padding) have l == 0; emit zeros
            safe_l = jnp.where(l > 0.0, l, 1.0)
            o_ref[0, 0] = (acc_ref[:] / safe_l[:, None]).astype(o_ref.dtype)
            lse_ref[0, 0, :, 0] = m_ref[:, 0] + jnp.log(safe_l)

    def fwd(q, k, v):
        B, H = q.shape[0], q.shape[1]
        qspec = pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, i, j: (b, h, i, 0))
        kspec = pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, i, j: (b, h, j, 0))
        return pl.pallas_call(
            fwd_kernel,
            grid=(B, H, nq, nk),
            in_specs=[qspec, kspec, kspec],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, i, j: (b, h, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
                jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v)

    # -- backward: dQ (accumulates over k-blocks) ------------------------

    def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  dq_ref, acc_ref):
        qi, kj = pl.program_id(2), pl.program_id(3)

        @pl.when(kj == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        @pl.when(_live(qi, kj))
        def _():
            q = q_ref[0, 0]
            do = do_ref[0, 0]
            lse = lse_ref[0, 0, :, 0]
            delta = delta_ref[0, 0, :, 0]
            kb = k_ref[0, 0]
            vb = v_ref[0, 0]
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            p = jnp.where(_mask(qi, kj), jnp.exp(s - lse[:, None]), 0.0)
            dp = jax.lax.dot_general(
                do, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None]) * scale).astype(kb.dtype)
            acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(kj == nk - 1)
        def _():
            dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)

    def bwd_dq(q, k, v, do, lse, delta):
        B, H = q.shape[0], q.shape[1]
        qspec = pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, i, j: (b, h, i, 0))
        kspec = pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, i, j: (b, h, j, 0))
        vspec = pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, i, j: (b, h, i, 0))
        return pl.pallas_call(
            dq_kernel,
            grid=(B, H, nq, nk),
            in_specs=[qspec, kspec, kspec, qspec, vspec, vspec],
            out_specs=qspec,
            out_shape=jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            interpret=interpret,
        )(q, k, v, do, lse, delta)

    # -- backward: dK, dV (accumulates over q-blocks) --------------------

    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_acc, dv_acc):
        kj, qi = pl.program_id(2), pl.program_id(3)

        @pl.when(qi == 0)
        def _():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        @pl.when(_live(qi, kj))
        def _():
            kb = k_ref[0, 0]
            vb = v_ref[0, 0]
            qb = q_ref[0, 0]
            dob = do_ref[0, 0]
            lse = lse_ref[0, 0, :, 0]
            delta = delta_ref[0, 0, :, 0]
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            p = jnp.where(_mask(qi, kj), jnp.exp(s - lse[:, None]), 0.0)
            pb = p.astype(dob.dtype)
            dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
                pb, dob, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                dob, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None]) * scale).astype(qb.dtype)
            dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(qi == nq - 1)
        def _():
            dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)

    def bwd_dkv(q, k, v, do, lse, delta):
        B, H = q.shape[0], q.shape[1]
        qspec = pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, j, i: (b, h, i, 0))
        kspec = pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, j, i: (b, h, j, 0))
        vspec = pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, j, i: (b, h, i, 0))
        return pl.pallas_call(
            dkv_kernel,
            grid=(B, H, nk, nq),
            in_specs=[qspec, kspec, kspec, qspec, vspec, vspec],
            out_specs=[kspec, kspec],
            out_shape=[jax.ShapeDtypeStruct((B, H, Tk, D), k.dtype),
                       jax.ShapeDtypeStruct((B, H, Tk, D), v.dtype)],
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32)],
            interpret=interpret,
        )(q, k, v, do, lse, delta)

    return fwd, bwd_dq, bwd_dkv


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """Memory-efficient exact attention; drop-in for ``dense_attention``.

    ``q, k, v``: ``[B, T, H, D]`` (q and k/v sequence lengths may
    differ; with ``causal`` the queries are taken as the LAST ``Tq``
    positions of the key sequence — the kv-cache decode convention).
    Scores are scaled by ``1/sqrt(D)``. Differentiable via a custom VJP
    whose backward runs as Pallas kernels (probabilities recomputed
    from the saved logsumexp — no quadratic residual).

    NOTE for multi-device use: a Pallas kernel has no SPMD partitioning
    rule, so under jit with sharded operands it must be wrapped in
    shard_map (attention is independent per batch and head; see
    ``models.transformer.make_attention(mesh=...)``).
    """
    import jax
    import jax.numpy as jnp

    from geomx_tpu.ops import pallas_interpret

    if q.ndim != 4:
        raise ValueError(f"expected [B, T, H, D] tensors, got {q.shape}")
    Tq, Tk = q.shape[1], k.shape[1]
    if causal and Tq > Tk:
        # no decode-convention alignment exists for more queries than
        # keys; without this check, q rows with zero visible keys would
        # silently emit the value-block mean (online-softmax artifact)
        raise ValueError(
            f"causal attention needs Tq <= Tk, got Tq={Tq} > Tk={Tk}")
    bq, bk = min(block_q, _round_up(Tq, 8)), min(block_k, _round_up(Tk, 8))
    interpret = pallas_interpret()

    @jax.custom_vjp
    def _attn(q, k, v):
        return _attn_fwd(q, k, v)[0]

    def _to_bhtd(x):
        return jnp.transpose(x, (0, 2, 1, 3))

    def _pad_t(x, t_to):
        pad = t_to - x.shape[2]
        if pad == 0:
            return x
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))

    def _attn_fwd(q, k, v):
        qt, kt, vt = _to_bhtd(q), _to_bhtd(k), _to_bhtd(v)
        Tqp, Tkp = _round_up(Tq, bq), _round_up(Tk, bk)
        qt, kt, vt = _pad_t(qt, Tqp), _pad_t(kt, Tkp), _pad_t(vt, Tkp)
        fwd, _, _ = _kernels(Tqp, Tkp, q.shape[3], bq, bk, causal, Tq,
                             Tk, interpret)
        o, lse = fwd(qt, kt, vt)
        out = jnp.transpose(o[:, :, :Tq], (0, 2, 1, 3))
        return out, (q, k, v, out, lse[:, :, :Tq, 0])

    def _attn_bwd(res, g):
        q, k, v, out, lse = res
        qt, kt, vt = _to_bhtd(q), _to_bhtd(k), _to_bhtd(v)
        dot, ot = _to_bhtd(g), _to_bhtd(out)
        Tqp, Tkp = _round_up(Tq, bq), _round_up(Tk, bk)
        delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                        axis=-1)                       # [B, H, Tq]
        if Tqp != Tq:
            pad = ((0, 0), (0, 0), (0, Tqp - Tq))
            delta = jnp.pad(delta, pad)
            lse = jnp.pad(lse, pad)
        qt, dot = _pad_t(qt, Tqp), _pad_t(dot, Tqp)
        kt, vt = _pad_t(kt, Tkp), _pad_t(vt, Tkp)
        _, bwd_dq, bwd_dkv = _kernels(Tqp, Tkp, q.shape[3], bq, bk,
                                      causal, Tq, Tk, interpret)
        lse4, delta4 = lse[..., None], delta[..., None]
        dq = bwd_dq(qt, kt, vt, dot, lse4, delta4)
        dk, dv = bwd_dkv(qt, kt, vt, dot, lse4, delta4)
        tr = lambda x, t: jnp.transpose(x[:, :, :t], (0, 2, 1, 3))
        return tr(dq, Tq), tr(dk, Tk), tr(dv, Tk)

    _attn.defvjp(_attn_fwd, _attn_bwd)
    return _attn(q, k, v)


def make_sharded_flash_attention(mesh, *, causal: bool = True,
                                 block_q: int = 128, block_k: int = 128):
    """shard_map-wrap :func:`flash_attention` over ``mesh`` (dp/tp).

    A Pallas kernel has no SPMD partitioning rule, so under jit with
    sharded operands the kernel must run per-shard. Attention is
    independent per batch ("dp") and head ("tp"); sequence-sharded
    meshes ("sp" > 1) need ring attention instead and are rejected.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    if "sp" in mesh.axis_names and mesh.shape["sp"] > 1:
        raise ValueError(
            "flash attention cannot shard the sequence axis; "
            "use parallel.make_ring_attention for sp > 1")
    fn = functools.partial(flash_attention, causal=causal,
                           block_q=block_q, block_k=block_k)
    spec = P(("dp",) if "dp" in mesh.axis_names else None, None,
             "tp" if "tp" in mesh.axis_names else None, None)
    # check_vma=False: pallas_call outputs carry no varying-mesh-axes
    # annotation, and the kernel touches no collectives
    return jax.shard_map(lambda q, k, v: fn(q, k, v), mesh=mesh,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)
