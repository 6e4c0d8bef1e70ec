"""FlashAttention-2 as Pallas TPU kernels (forward + backward).

The reference has no attention operator at all (SURVEY.md §5.7 — its op
set predates attention-era models); this is part of the long-context
capability the TPU build adds as first-class. The kernel keeps both the
O(T^2) score matrix AND full-sequence K/V residency out of on-chip
memory: the key/value blocks ride the innermost grid dimension, so each
program instance holds one (block_q, D) query tile, one (block_k, D)
key/value tile, and fp32 VMEM scratch accumulators carrying the
online-softmax running (max, sumexp) state of FlashAttention-2 across
grid steps.
The backward recomputes probabilities blockwise from the saved
logsumexp (no quadratic residual) in ONE kernel: a live tile's scores,
probabilities, dP and dS are computed once and feed dQ, dK and dV
together (five block products a tile; a dQ kernel beside a dK/dV kernel
took seven and ran the softmax arithmetic twice). dQ is summed over
k-blocks and dK, dV over q-blocks, so no grid order has both sums
innermost: each cotangent is summed in float32 VMEM scratch that holds
a head's whole sequence and is written back once a head. The forward's
VMEM is O(block^2) whatever the length; the backward's grows with it,
(4 + 2 * 2) * (Tq * D + Tk * (D + Dv)) bytes for bfloat16 operands
(scratch, and the outputs' blocks twice under the pipeline's double
buffer): 34 MB at T 8,192 with heads of 192 / 128, the largest caller,
and past 16,384 positions at heads of 128 the compiler refuses it (the
kernels may take 64 MiB of VMEM).

Layout contract matches ``geomx_tpu.models.transformer.dense_attention``:
``q, k, v`` are ``[B, T, H, D]`` and the return is ``[B, T, H, D]``
(``v`` and the return ``[B, T, H, Dv]`` where a value head has its own
size).
Sequence lengths that are not multiples of the block size are
zero-padded; padded keys are masked out of the softmax and padded query
rows are sliced off (their cotangents are zero in the backward pass, so
they contribute nothing to dK/dV).

The logsumexp rides through the kernels as ``[B, H, T, 1]`` — TPU block
shapes must keep their last two dims (8, 128)-aligned or equal to the
full array dims, which a trailing singleton satisfies for vectors.

Masks: the kernels take three, all static and all with dead blocks
neither computed nor fetched and a mask built only on the blocks a
boundary crosses: causal with an offset (key j <= query i + offset),
the block mask of block-diffusion training over a clean and a noised
copy of one sequence side by side (:func:`_block_rule`), and the
sliding window (query i sees the keys j with 0 <= i - j < w;
:func:`_window_rule`), under which the inner grid dimension is the
band's own count of blocks and not the sequence's: a grid step costs
the same whatever its tile, and most of a long sequence's tiles lie
outside the band. No bias or dropout.

Interpret mode is chosen by ``ops.pallas_interpret()`` alone: compiled on
a TPU backend, interpreted elsewhere — which is what the CPU test suite
exercises against the dense reference.
"""

from __future__ import annotations

import functools

__all__ = ["flash_attention", "make_sharded_flash_attention",
           "attention_blocks", "live_blocks", "block_mask_live_blocks",
           "window_live_blocks"]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def attention_blocks(t: int, d: int, window=None):
    """(block_q, block_k) of the kernels for ``t`` positions and heads
    of size ``d`` under a sliding ``window`` or none, from the three
    alone. Read on a v5e (PERF.md section 6, PRs 39 and 50;
    ``tools/attention_bench.py blocks``): a grid step costs the same
    whatever its tile, so small tiles are bound by the grid (128 x 128
    is five times slower than 512 x 512), and past 512 x 1024 the gain
    is under 5% while the float32 score tile's temporaries grow with
    the area and the causal diagonal wastes more of it. So 512 queries
    against 1,024 keys at heads up to 128, against 512 over that (a
    head's K/V tile doubles with ``d``) and under a window: a band of
    1,024 keys is three tiles of 512 a q-block and two of 1,024, 25%
    fewer entries for half as many steps again, and 512 x 512 read 8%
    (window 1,024 at T 8,192) and 15% (512 at 4,096) under 512 x 1,024,
    256 x 256 and 1,024 x 1,024 over both. A shorter sequence is one
    block, rounded up to the sublane tile."""
    block_q = min(512, _round_up(t, 8))
    block_k = min(1024 if d <= 128 and window is None else 512,
                  _round_up(t, 8))
    return block_q, block_k


def live_blocks(t: int, block_q: int, block_k: int) -> int:
    """(q-block, k-block) pairs of a causal [t, t] product that hold an
    entry the mask keeps: the tiles the kernels compute, every other one
    is skipped. ``live_blocks * block_q * block_k`` are the score
    entries a head computes a pass."""
    nq, nk = -(-t // block_q), -(-t // block_k)
    return sum(min(nk, -(-(i + 1) * block_q // block_k)) for i in range(nq))


def _seen(x, intervals, xp):
    """The member of the union of ``intervals`` ((lo, hi, non-empty)
    triples of block indices) that a sweep standing at ``x`` has in
    hand: ``x`` itself inside one, else the last member before ``x``,
    else the first after it."""
    before, first = -1, 2 ** 30
    for lo, hi, some in intervals:
        before = xp.maximum(before, xp.where(
            some & (lo <= x), xp.minimum(hi, x), -1))
        first = xp.minimum(first, xp.where(some, lo, 2 ** 30))
    return xp.where(before >= 0, before, first)


def _block_rule(xp, t: int, b: int, block_q: int, block_k: int):
    """The block mask of block-diffusion training over ``2 * t``
    positions, the clean copy of a sequence before its noised copy, in
    blocks of ``b`` positions: a clean query sees the clean keys of the
    blocks up to and including its own, a noised query the clean keys
    of the blocks strictly before its own and the noised keys of its
    own block. Every answer is written with the START of a position's
    block (``x // b * b``), so a row's keys are two ranges: clean row
    i: [0, start(i) + b); noised row t + l: [0, start(l)) and
    t + [start(l), start(l) + b). Returns the kernels' five questions,
    over ``xp`` (``jax.numpy`` on the grid's traced indices,
    ``numpy`` for counting by hand): ``mask(qpos, kpos)`` elementwise;
    ``live(qi, kj)``, ``whole(qi, kj)`` a tile; ``k_seen``, ``q_seen``
    the tile a sweep fetches (a dead tile's index is that of the live
    one in hand)."""
    n = 2 * t

    def start(x):
        return x // b * b

    def tile(i, size):
        """Tile ``i`` of ``size`` positions, padding left out: (has
        clean positions, its first, has noised positions, the first and
        the last of them in the copy's own numbering)."""
        first = i * size
        last = xp.minimum(first + size, n) - 1
        return first < t, first, last >= t, xp.maximum(first, t) - t, last - t

    def k_intervals(qi):
        """k-blocks that hold a key some row of q-block ``qi`` sees:
        the clean keys [0, end) and the noised keys [lo, hi)."""
        has_c, q0, has_n, n_lo, n_hi = tile(qi, block_q)
        c_hi = xp.minimum(q0 + block_q, t) - 1
        end = xp.maximum(
            xp.where(has_c, xp.minimum(start(c_hi) + b, t), 0),
            xp.where(has_n, start(n_hi), 0))
        lo = t + start(n_lo)
        hi = t + xp.minimum(start(n_hi) + b, t)
        return ((0 * qi, (end - 1) // block_k, end > 0),
                (lo // block_k, (hi - 1) // block_k, has_n))

    def q_intervals(kj):
        """q-blocks that hold a row which sees some key of k-block
        ``kj``: clean rows from the block of its first clean key on,
        noised rows of the blocks behind that one, and the noised rows
        of its noised keys' blocks."""
        has_c, k0, has_n, n_lo, n_hi = tile(kj, block_k)
        behind = start(k0) + b
        last = xp.minimum(start(n_hi) + b, t) - 1
        return ((start(k0) // block_q, (t - 1) // block_q + 0 * kj, has_c),
                ((t + behind) // block_q, (n - 1) // block_q + 0 * kj,
                 has_c & (behind < t)),
                ((t + start(n_lo)) // block_q, (t + last) // block_q,
                 has_n))

    def mask(qpos, kpos):
        clean_q, clean_k = qpos < t, kpos < t
        own = start(xp.where(clean_q, qpos, qpos - t))
        # no select between masks: Mosaic has none for vectors of bits
        return (kpos < n) & (
            (clean_k & (kpos < xp.where(clean_q, own + b, own)))
            | (~clean_q & (kpos >= t + own) & (kpos < t + own + b)))

    def live(qi, kj):
        (_, c_hi, some_c), (n_lo, n_hi, some_n) = k_intervals(qi)
        return (some_c & (kj <= c_hi)) | (some_n & (n_lo <= kj) & (kj <= n_hi))

    def whole(qi, kj):
        has_c, q0, has_n, n_lo, n_hi = tile(qi, block_q)
        k0, end = kj * block_k, (kj + 1) * block_k
        clean_keys = (~has_c | (end <= start(q0) + b)) \
            & (~has_n | (end <= start(n_lo)))
        noised_keys = ~has_c & (k0 >= t + start(n_hi)) \
            & (end <= t + start(n_lo) + b)
        return (end <= n) & xp.where(end <= t, clean_keys, noised_keys)

    return (mask, live, whole,
            lambda qi, kj: _seen(kj, k_intervals(qi), xp),
            lambda qi, kj: _seen(qi, q_intervals(kj), xp))


def block_mask_live_blocks(t: int, b: int, block_q: int,
                           block_k: int) -> int:
    """(q-block, k-block) pairs of the block mask over ``2 * t``
    positions in blocks of ``b`` (:func:`_block_rule`) that hold an
    entry the mask keeps: the tiles the kernels compute."""
    import numpy as np

    block_q, block_k = (min(x, _round_up(2 * t, 8))
                        for x in (block_q, block_k))
    live = _block_rule(np, t, b, block_q, block_k)[1]
    qi, kj = np.meshgrid(np.arange(-(-2 * t // block_q)),
                         np.arange(-(-2 * t // block_k)), indexing="ij")
    return int(live(qi, kj).sum())


def _window_rule(xp, t: int, w: int, block_q: int, block_k: int):
    """The sliding window over ``t`` positions: query i sees the keys j
    with ``0 <= i - j < w``, ``models.transformer.window_attention``'s
    mask. A q-block's keys are ONE run of k-blocks and a k-block's
    queries one run of q-blocks, each from the block's first position
    to its last real one, so the answers are: ``mask(qpos, kpos)``
    elementwise; ``live(qi, kj)``, ``whole(qi, kj)`` a tile (whole:
    every entry of its real rows is kept); ``k_band(qi)``,
    ``q_band(kj)`` the (first, last) block of the run, which is what a
    sweep covers. Over ``xp`` as :func:`_block_rule`."""
    def k_band(qi):
        q0, q1 = qi * block_q, xp.minimum((qi + 1) * block_q, t) - 1
        return xp.maximum(q0 - w + 1, 0) // block_k, q1 // block_k

    def q_band(kj):
        k0, k1 = kj * block_k, xp.minimum((kj + 1) * block_k, t) - 1
        return k0 // block_q, xp.minimum(k1 + w - 1, t - 1) // block_q

    def mask(qpos, kpos):
        return (kpos < t) & (qpos >= kpos) & (qpos - kpos < w)

    def live(qi, kj):
        # a sweep's last steps may stand past the last q-block
        first, last = k_band(qi)
        return (qi * block_q < t) & (first <= kj) & (kj <= last)

    def whole(qi, kj):
        q0, q1 = qi * block_q, xp.minimum((qi + 1) * block_q, t) - 1
        k0, k1 = kj * block_k, (kj + 1) * block_k - 1
        return (k1 < t) & (k1 <= q0) & (q1 - k0 < w)

    return mask, live, whole, k_band, q_band


def _window_sweeps(t: int, w: int, block_q: int, block_k: int):
    """(k-blocks a q-block's sweep covers, q-blocks a k-block's) under
    the window: the longest run of :func:`_window_rule`, the same for
    every block but those the sequence's ends cut short."""
    import numpy as np

    k_band, q_band = _window_rule(np, t, w, block_q, block_k)[3:]
    first, last = k_band(np.arange(-(-t // block_q)))
    k_steps = int((last - first).max()) + 1
    first, last = q_band(np.arange(-(-t // block_k)))
    return k_steps, int((last - first).max()) + 1


def window_live_blocks(t: int, w: int, block_q: int, block_k: int) -> int:
    """(q-block, k-block) pairs of a [t, t] product under the sliding
    window ``w`` (:func:`_window_rule`) that hold an entry the mask
    keeps: the tiles the kernels compute."""
    import numpy as np

    block_q, block_k = (min(x, _round_up(t, 8)) for x in (block_q, block_k))
    first, last = _window_rule(np, t, w, block_q, block_k)[3](
        np.arange(-(-t // block_q)))
    return int((last - first + 1).sum())


@functools.lru_cache(maxsize=None)
def _kernels(Tq: int, Tk: int, D: int, Dv: int, block_q: int, block_k: int,
             rule, q_len: int, kv_len: int, group: int, interpret: bool):
    """Build (fwd, bwd) pallas_calls for one static shape and one
    static mask, ``rule``: ``True`` causal, ``False`` none,
    ``(t, b)``, the block mask of :func:`_block_rule` over ``q_len ==
    kv_len == 2 * t`` positions, or ``("window", w)``, the sliding
    window of :func:`_window_rule` over ``q_len == kv_len`` positions.

    Both work on ``[B, H, T, D]``-transposed arrays (``v``, ``o``
    and their cotangents ``[B, H, T, Dv]``: a value head has its own
    size, the scores and their scale are over ``D``); ``k`` and
    ``v`` have ``H / group`` heads, query head ``h`` reads key/value
    head ``h // group``. The forward's grid is (batch, head, q-block,
    k-block), the backward's (batch, key/value head, the head's
    ``group`` query heads, k-block, q-block), the inner dimensions
    iterated sequentially on-core, both accumulating into VMEM scratch
    (the backward's holds a head's whole sequence: dQ is summed over a
    query head's k-blocks ascending, dK and dV over the group's heads,
    then their q-blocks ascending). Under the window the inner
    dimension counts the steps of a block's band
    (:func:`_window_sweeps`) and a step's tile is the band's first plus
    the step. ``q_len`` <= Tq and ``kv_len`` <= Tk are the true
    (unpadded) lengths; keys past ``kv_len`` are masked out.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale = 1.0 / (D ** 0.5)
    nq = Tq // block_q
    nk = Tk // block_k
    neg_inf = -1e30

    causal = rule is True
    # decode convention: when Tq != Tk the queries are the LAST q_len
    # positions of the key sequence (kv-cache decode), so q row i sits at
    # absolute position i + (kv_len - q_len)
    causal_offset = kv_len - q_len
    window = not isinstance(rule, bool) and rule[0] == "window"
    block = not isinstance(rule, bool) and not window
    # steps of the inner grid dimension: a q-block's sweep over k-blocks
    # (forward), a k-block's over q-blocks (backward)
    k_steps, q_steps = nk, nq
    if block:
        (rule_mask, rule_live, rule_whole, block_k_seen,
         block_q_seen) = _block_rule(jnp, *rule, block_q, block_k)
    if window:
        (rule_mask, rule_live, rule_whole, k_band,
         q_band) = _window_rule(jnp, kv_len, rule[1], block_q, block_k)
        k_steps, q_steps = _window_sweeps(kv_len, rule[1], block_q, block_k)

    def _mask(qi, kj):
        """[block_q, block_k] validity mask for q-block qi, k-block kj."""
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if block or window:
            return rule_mask(qpos, kpos)
        m = kpos < kv_len
        if causal:
            m = m & (qpos + causal_offset >= kpos)
        return m

    def _live(qi, kj):
        """Does (q-block qi, k-block kj) contribute at all?"""
        if block or window:
            return rule_live(qi, kj)
        if not causal:
            return True
        return kj * block_k < (qi + 1) * block_q + causal_offset

    def _whole(qi, kj):
        """Is every entry of the block valid (no mask to apply)?"""
        if block or window:
            return rule_whole(qi, kj)
        inside = (kj + 1) * block_k <= kv_len
        if not causal:
            return inside
        return inside & ((kj + 1) * block_k - 1
                         <= qi * block_q + causal_offset)

    def _on_live_block(qi, kj, body):
        """Run ``body(mask or None)`` for a live block: the mask is
        built only where the block crosses a boundary of the mask or
        the padding."""
        whole = _whole(qi, kj)

        @pl.when(_live(qi, kj) & jnp.logical_not(whole))
        def _():
            body(_mask(qi, kj))

        @pl.when(_live(qi, kj) & whole)
        def _():
            body(None)

    def _k_at(qi, j):
        """The k-block at step ``j`` of q-block ``qi``'s sweep."""
        return k_band(qi)[0] + j if window else j

    def _q_at(i, kj):
        """The q-block at step ``i`` of k-block ``kj``'s sweep."""
        return q_band(kj)[0] + i if window else i

    # a dead block's tile takes the index of the live one beside it, so
    # the pipeline sees no new block and issues no DMA for it (the
    # window's sweeps take a step, the others the block itself)
    def _k_seen(qi, kj):
        if window:
            first, last = k_band(qi)
            return jnp.minimum(first + kj, last)
        if block:
            return jnp.clip(block_k_seen(qi, kj), 0, nk - 1)
        if not causal:
            return kj
        last = ((qi + 1) * block_q + causal_offset - 1) // block_k
        return jnp.minimum(kj, jnp.clip(last, 0, nk - 1))

    def _q_seen(qi, kj):
        if window:
            first, last = q_band(kj)
            return jnp.minimum(first + qi, last)
        if block:
            return jnp.clip(block_q_seen(qi, kj), 0, nq - 1)
        if not causal:
            return qi
        first = (kj * block_k - causal_offset) // block_q
        return jnp.maximum(qi, jnp.clip(first, 0, nq - 1))

    def _params(parallel: int, sequential: int):
        if interpret:
            return {}
        return dict(compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * parallel
            + ("arbitrary",) * sequential,
            vmem_limit_bytes=64 * 1024 * 1024))

    # -- forward ---------------------------------------------------------
    # grid (B, H, nq, k_steps): k-blocks innermost; acc/m/l scratch
    # persists across the k sweep for one q-block, finalized at the last
    # k step.

    def fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                   acc_ref, m_ref, l_ref):
        qi, step = pl.program_id(2), pl.program_id(3)
        kj = _k_at(qi, step)

        @pl.when(step == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, neg_inf)
            l_ref[:] = jnp.zeros_like(l_ref)

        def body(mask):
            # matmul operands stay in the INPUT dtype (bf16 runs the MXU
            # at full rate; an up-front f32 cast would halve it) with
            # f32 accumulation; softmax math is f32
            q = q_ref[0, 0]
            kb = k_ref[0, 0]
            vb = v_ref[0, 0]
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if mask is not None:
                s = jnp.where(mask, s, neg_inf)
            m = m_ref[:]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:] = m_new

        _on_live_block(qi, kj, body)

        @pl.when(step == k_steps - 1)
        def _():
            l = l_ref[:]
            # rows with no valid key (padding) have l == 0; emit zeros
            safe_l = jnp.where(l > 0.0, l, 1.0)
            o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
            lse_ref[0, 0] = m_ref[:] + jnp.log(safe_l)

    def fwd(q, k, v):
        B, H = q.shape[0], q.shape[1]
        qspec = pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, i, j: (b, h, i, 0))
        kspec, vspec = (pl.BlockSpec(
            (1, 1, block_k, d),
            lambda b, h, i, j: (b, h // group, _k_seen(i, j), 0))
            for d in (D, Dv))
        return pl.pallas_call(
            fwd_kernel,
            grid=(B, H, nq, k_steps),
            in_specs=[qspec, kspec, vspec],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, Dv),
                             lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, i, j: (b, h, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, Tq, Dv), q.dtype),
                jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, Dv), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
            interpret=interpret, **_params(3, 1),
        )(q, k, v)

    def _p_and_ds(q, kb, vb, do, lse, delta, mask):
        """A block's probabilities and score cotangents, recomputed
        from the saved logsumexp: (p float32, ds in the operands'
        type)."""
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return p, (p * (dp - delta) * scale).astype(kb.dtype)

    # -- backward --------------------------------------------------------
    # grid (B, KV, group, nk, q_steps). A live tile's s, p, dP and dS are
    # computed once and feed all three cotangents, each summed in scratch
    # over a head's WHOLE sequence. An output's block is the whole
    # sequence too, its index constant over the dimensions its cotangent
    # is summed over: it stays in VMEM, takes the cast sum at the head's
    # last step and leaves once.

    def bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc):
        g, kj, step = pl.program_id(2), pl.program_id(3), pl.program_id(4)
        qi = _q_at(step, kj)
        first = (kj == 0) & (step == 0)
        last = (kj == nk - 1) & (step == q_steps - 1)

        @pl.when(first)
        def _():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        @pl.when(first & (g == 0))
        def _():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        def body(mask):
            qb, kb, dob = q_ref[0, 0], k_ref[0, 0], do_ref[0, 0]
            p, ds = _p_and_ds(qb, kb, v_ref[0, 0], dob, lse_ref[0, 0],
                              delta_ref[0, 0], mask)
            q_rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
            k_rows = pl.ds(pl.multiple_of(kj * block_k, block_k), block_k)
            dv_acc[k_rows] += jax.lax.dot_general(
                p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[k_rows] += jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_acc[q_rows] += jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _on_live_block(qi, kj, body)

        @pl.when(last)
        def _():
            dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)

        @pl.when(last & (g == group - 1))
        def _():
            dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)

    def bwd(q, k, v, do, lse, delta):
        B, KV = k.shape[0], k.shape[1]
        qspec, dospec, rowspec = (pl.BlockSpec(
            (1, 1, block_q, d),
            lambda b, h, g, j, i: (b, h * group + g, _q_seen(i, j), 0))
            for d in (D, Dv, 1))
        kspec, vspec = (pl.BlockSpec((1, 1, block_k, d),
                                     lambda b, h, g, j, i: (b, h, j, 0))
                        for d in (D, Dv))
        return pl.pallas_call(
            bwd_kernel,
            grid=(B, KV, group, nk, q_steps),
            in_specs=[qspec, kspec, vspec, dospec, rowspec, rowspec],
            out_specs=[
                pl.BlockSpec((1, 1, Tq, D),
                             lambda b, h, g, j, i: (b, h * group + g, 0, 0)),
                pl.BlockSpec((1, 1, Tk, D),
                             lambda b, h, g, j, i: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, Tk, Dv),
                             lambda b, h, g, j, i: (b, h, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, KV * group, Tq, D), q.dtype),
                jax.ShapeDtypeStruct((B, KV, Tk, D), k.dtype),
                jax.ShapeDtypeStruct((B, KV, Tk, Dv), v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((Tq, D), jnp.float32),
                            pltpu.VMEM((Tk, D), jnp.float32),
                            pltpu.VMEM((Tk, Dv), jnp.float32)],
            interpret=interpret, **_params(2, 3),
        )(q, k, v, do, lse, delta)

    if window:
        # every layer of a model calls these at one shape: under a jit
        # of their own a program traces and lowers each kernel once and
        # calls it, where a bare pallas_call is traced and lowered anew
        # at every call (set-up time: PERF.md section 6, PR 50)
        return jax.jit(fwd), jax.jit(bwd)
    return fwd, bwd


def flash_attention(q, k, v, *, causal: bool = True, block_mask=None,
                    window=None, block_q=None, block_k=None):
    """Memory-efficient exact attention; drop-in for ``dense_attention``
    and, with grouped queries, for ``grouped_attention``.

    ``q, k, v``: ``[B, T, H, D]`` (q and k/v sequence lengths may
    differ; with ``causal`` the queries are taken as the LAST ``Tq``
    positions of the key sequence — the kv-cache decode convention).
    Grouped queries: ``k`` and ``v`` ``[B, T, KV, D]`` under ``q``
    ``[B, T, KV * G, D]`` or ``[B, T, KV, G, D]`` (the return has q's
    shape): query head ``h`` reads key/value head ``h // G``, which is
    never repeated; dK and dV are summed over the group inside the
    kernel. A value head may have another size than a query/key head
    (``v`` ``[B, T, KV, Dv]``; the return then ``[B, T, H, Dv]``: the
    form latent attention has, 192 beside 128). ``block_mask`` ``(t,
    b)`` in place of ``causal``: the sequence axis holds the clean and
    the noised copy of ``t`` positions, ``2 * t`` queries and keys,
    under the block mask of block-diffusion training in blocks of ``b``
    (:func:`_block_rule`). ``window`` ``w`` in place of both: query i
    sees the keys j with ``0 <= i - j < w`` (:func:`_window_rule`), as
    many queries as keys. Scores are scaled by ``1/sqrt(D)``.
    ``block_q`` / ``block_k``: :func:`attention_blocks` unless given.
    Differentiable
    via a custom VJP whose backward runs as Pallas kernels
    (probabilities recomputed from the saved logsumexp — no quadratic
    residual).

    NOTE for multi-device use: a Pallas kernel has no SPMD partitioning
    rule, so under jit with sharded operands it must be wrapped in
    shard_map (attention is independent per batch and head; see
    ``models.transformer.make_attention(mesh=...)``).
    """
    import jax
    import jax.numpy as jnp

    from geomx_tpu.ops import pallas_interpret

    if q.ndim == 5:
        b, t, kv, g, d = q.shape
        return flash_attention(
            q.reshape(b, t, kv * g, d), k, v, causal=causal,
            block_mask=block_mask, window=window, block_q=block_q,
            block_k=block_k).reshape(
                b, t, kv, g, v.shape[-1])
    if q.ndim != 4:
        raise ValueError(f"expected [B, T, H, D] tensors, got {q.shape}")
    Tq, Tk = q.shape[1], k.shape[1]
    group = q.shape[2] // k.shape[2]
    if (k.shape[:3] != v.shape[:3] or k.shape[3] != q.shape[3]
            or q.shape[2] != group * k.shape[2]):
        raise ValueError(f"query heads {q.shape} are not groups of the "
                         f"key/value heads {k.shape}, {v.shape}")
    if block_mask is not None:
        if not Tq == Tk == 2 * block_mask[0]:
            raise ValueError(
                f"the block mask {block_mask} is over {2 * block_mask[0]} "
                f"queries and keys, got {Tq} and {Tk}")
    elif window is not None:
        if Tq != Tk or window < 1:
            raise ValueError(
                f"a window is one key or more over as many queries as "
                f"keys, got {window} over {Tq} and {Tk}")
    elif causal and Tq > Tk:
        # no decode-convention alignment exists for more queries than
        # keys; without this check, q rows with zero visible keys would
        # silently emit the value-block mean (online-softmax artifact)
        raise ValueError(
            f"causal attention needs Tq <= Tk, got Tq={Tq} > Tk={Tk}")
    D = q.shape[3]
    bq = min(block_q or attention_blocks(Tq, D, window)[0], _round_up(Tq, 8))
    bk = min(block_k or attention_blocks(Tk, D, window)[1], _round_up(Tk, 8))
    Tqp, Tkp = _round_up(Tq, bq), _round_up(Tk, bk)
    if block_mask is not None:
        rule = tuple(block_mask)
    else:
        rule = causal if window is None else ("window", int(window))
    fwd, bwd = _kernels(Tqp, Tkp, D, v.shape[3], bq, bk, rule, Tq, Tk,
                        group, pallas_interpret())

    @jax.custom_vjp
    def _attn(q, k, v):
        return _attn_fwd(q, k, v)[0]

    def _to_bhtd(x, t_to):
        x = jnp.transpose(x, (0, 2, 1, 3))
        return jnp.pad(x, ((0, 0), (0, 0), (0, t_to - x.shape[2]), (0, 0)))

    def _to_bthd(x, t):
        return jnp.transpose(x[:, :, :t], (0, 2, 1, 3))

    def _attn_fwd(q, k, v):
        o, lse = fwd(_to_bhtd(q, Tqp), _to_bhtd(k, Tkp), _to_bhtd(v, Tkp))
        out = _to_bthd(o, Tq)
        return out, (q, k, v, out, lse[:, :, :Tq, 0])

    def _attn_bwd(res, g):
        q, k, v, out, lse = res
        dot = _to_bhtd(g, Tqp)
        delta = jnp.sum(dot.astype(jnp.float32)
                        * _to_bhtd(out, Tqp).astype(jnp.float32),
                        axis=-1, keepdims=True)         # [B, H, Tqp, 1]
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, Tqp - Tq)))[..., None]
        dq, dk, dv = bwd(_to_bhtd(q, Tqp), _to_bhtd(k, Tkp),
                         _to_bhtd(v, Tkp), dot, lse, delta)
        return _to_bthd(dq, Tq), _to_bthd(dk, Tk), _to_bthd(dv, Tk)

    _attn.defvjp(_attn_fwd, _attn_bwd)
    return _attn(q, k, v)


def make_sharded_flash_attention(mesh, *, causal: bool = True,
                                 block_q=None, block_k=None):
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
