"""Decoder-only transformer with pluggable (ring) attention.

The long-context/distributed flagship: batch shards over "dp", sequence
over "sp" (ring attention via shard_map+ppermute), heads and MLP hidden
over "tp" (Megatron-style, via parameter shardings that GSPMD propagates).
The reference has no attention-era model layer at all (SURVEY.md §5.7);
this is the capability the TPU build adds as first-class.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


HIGHEST = jax.lax.Precision.HIGHEST
FULL = "full_attention"     # HF's name of a layer whose keys are all j <= i


class RMSNorm(nn.Module):
    """``x / rms(x) * scale`` over the last axis; ``zero_centred``: the
    parameter is the scale's distance from 1, ``x / rms(x) * (1 + w)``,
    zero at the start (the form Qwen3-Next publishes)."""
    eps: float
    dtype: Any = jnp.float32
    zero_centred: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.zeros if self.zero_centred
            else nn.initializers.ones, (x.shape[-1],), jnp.float32)
        if self.zero_centred:
            scale = 1.0 + scale
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps)
        return (x * scale).astype(self.dtype)


def make_attention(impl: str = "auto", *, causal: bool = True,
                   mesh: Optional[Mesh] = None,
                   block_q: Optional[int] = None,
                   block_k: Optional[int] = None) -> Callable:
    """Attention implementation selector for ``Transformer(attn_fn=...)``.

    ``"flash"`` — the Pallas FlashAttention-2 kernels
    (geomx_tpu.ops.flash_attention): no score matrix in memory,
    MXU-tiled, the choice for long sequences on TPU (its backward keeps
    a head's cotangents on chip: to 16,384 positions at heads of 128).
    ``"dense"`` — the XLA einsum reference. ``"auto"`` picks flash
    exactly where the kernels compile (``ops.pallas_interpret()``
    false, i.e. a TPU backend) and dense where they would only be
    interpreted.

    A Pallas kernel has no SPMD partitioning rule, so on a multi-device
    ``mesh`` the flash path must run under shard_map; attention is
    independent per batch ("dp") and head ("tp"), so pass the mesh and
    the kernel runs per-shard. (Sequence-sharded meshes need ring
    attention — ``parallel.make_ring_attention`` — not this hook.)
    """
    if impl == "auto":
        from geomx_tpu.ops import pallas_interpret

        impl = "dense" if pallas_interpret() else "flash"
    if impl == "flash":
        from geomx_tpu.ops.flash_attention import (
            flash_attention, make_sharded_flash_attention)

        if mesh is not None and mesh.devices.size > 1:
            return make_sharded_flash_attention(
                mesh, causal=causal, block_q=block_q, block_k=block_k)
        return lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    if impl == "dense":
        return lambda q, k, v: dense_attention(q, k, v, causal=causal)
    raise ValueError(f"unknown attention impl {impl!r}")


def dense_attention(q, k, v, *, causal: bool = True, scores_dtype=None):
    """Plain attention fallback (single-device / no sp axis).

    ``scores_dtype`` (e.g. float32 under bfloat16 operands) is the type
    the QK^T product accumulates into and the softmax runs in: logits of
    a few units, as q/k norms make them, lose their softmax to an 8-bit
    significand. ``None``: the operands' own type."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=scores_dtype)
    s = s / jnp.sqrt(d).astype(s.dtype)
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def grouped_attention(q, k, v, *, scores_dtype=None):
    """Causal attention with grouped queries: ``q`` [B, T, KV, G, D],
    ``k`` and ``v`` [B, T, KV, D]; the G query heads of a group share
    their key/value head, which is never repeated. The dense [T, T]
    product, ``scores_dtype`` as in :func:`dense_attention`. Returns
    [B, T, KV, G, D]."""
    t = q.shape[1]
    s = jnp.einsum("bqkgd,bjkd->bkgqj", q, k,
                   preferred_element_type=scores_dtype)
    s = s / jnp.sqrt(q.shape[-1]).astype(s.dtype)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgqj,bjkd->bqkgd", p.astype(v.dtype), v)


# The length from which attention runs as the kernel: read on a v5e
# (tools/attention_bench.py lengths; PERF.md section 6, PR 39)
KERNEL_MIN_T = 2048
# The sliding window from which it does: under it the blocked product's
# scores are few enough that XLA's form is as fast or faster (tools/
# attention_bench.py windows; PERF.md section 6, PR 50)
KERNEL_MIN_WINDOW = 512


def runs_kernel(q, forced: Optional[bool] = None,
                window: Optional[int] = None) -> bool:
    """THE rule for the form of attention over ``q`` [B, T, ...], full
    causal, under a block mask or under a sliding ``window``: the
    Pallas kernels of ``ops/flash_attention.py`` where they compile
    (``ops.pallas_interpret()`` false: a TPU backend), no mesh is in
    play, T is at or over :data:`KERNEL_MIN_T` (under it a head's
    scores are a few MB and the dense product is as fast) and a window
    at or over :data:`KERNEL_MIN_WINDOW` (under it the blocked product
    has as few); the dense product otherwise. It is one algorithm that
    wants another form at another length, so the rule reads what the
    trace can see and nothing names a model. A Pallas call has no
    partitioning rule, so a mesh means dense: seen as the abstract mesh
    of the context (``jax.set_mesh``, a ``shard_map`` body) or of
    ``q``'s own sharding (an operand sharded over explicit axes).
    Operands that GSPMD shards over ``Auto`` axes under a plain ``jit``
    show no mesh while tracing: such a caller passes its own
    ``attn_fn`` (:func:`make_attention` with its mesh). ``forced`` is
    for tests: the answer itself."""
    if forced is not None:
        return forced
    from geomx_tpu.ops import pallas_interpret

    return (not pallas_interpret()
            and jax.sharding.get_abstract_mesh().empty
            and jax.typeof(q).sharding.mesh.empty
            and q.shape[1] >= KERNEL_MIN_T
            and (window is None or window >= KERNEL_MIN_WINDOW))


def causal_attention(q, k, v):
    """Full causal attention with float32 scores, in the form
    :func:`runs_kernel` gives: ``q, k, v`` [B, T, H, D]
    (:func:`dense_attention`'s contract) or ``q`` [B, T, KV, G, D] on
    ``k``, ``v`` [B, T, KV, D] (:func:`grouped_attention`'s). The
    kernel's arithmetic is the dense product's: operands in their own
    type, the products accumulated and the softmax run in float32,
    the probabilities rounded to ``v``'s type before the second
    product. ``v``'s heads may have another size than ``q``'s and
    ``k``'s (:func:`latent_attention`). The default of OLMoE's,
    Laguna's and Qwen3-Next's full layers."""
    if runs_kernel(q):
        from geomx_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v)
    dense = grouped_attention if q.ndim == 5 else dense_attention
    return dense(q, k, v, scores_dtype=jnp.float32)


def window_blocks(t: int, window: int):
    """(block, number of blocks) of :func:`window_attention` at ``t``
    positions: a block is the window, or the sequence where that is
    shorter."""
    block = min(window, t)
    return block, -(-t // block)


def window_attention(q, k, v, window: int, *, scores_dtype=None):
    """Sliding-window attention with grouped queries, exact: query i
    sees the keys j with ``0 <= i - j < window``. Shapes as
    :func:`grouped_attention`. Blocked by the window: a block of
    ``window`` queries is multiplied with its own and the previous key
    block, which hold every key it may see, so the score product has
    ``2 * window`` columns a query whatever T; T is padded to whole
    blocks and the first block's predecessor is padding, both masked."""
    b, t, kv, g, d = q.shape
    block, nb = window_blocks(t, window)
    pad = nb * block - t
    q = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3)
    q = q.reshape(b, nb, block, kv, g, d)

    def with_previous(x):
        x = jnp.pad(x, ((0, 0), (block, pad), (0, 0), (0, 0)))
        x = x.reshape(b, nb + 1, block, kv, d)
        return jnp.concatenate([x[:, :-1], x[:, 1:]], axis=2)

    k, v = with_previous(k), with_previous(v)
    s = jnp.einsum("bnqkgd,bnjkd->bnkgqj", q, k,
                   preferred_element_type=scores_dtype)
    s = s / jnp.sqrt(d).astype(s.dtype)
    # column j of block n is position (n - 1) * block + j
    behind = (jnp.arange(block)[:, None] + block
              - jnp.arange(2 * block)[None])
    live = ((behind >= 0) & (behind < window))[None] & (
        (jnp.arange(nb)[:, None] > 0)
        | (jnp.arange(2 * block)[None] >= block))[:, None]
    s = jnp.where(live[None, :, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnkgqj,bnjkd->bnqkgd", p.astype(v.dtype), v)
    return o.reshape(b, nb * block, kv, g, d)[:, :t]


def score_entries(t: int, window: Optional[int] = None):
    """(live, computed) score entries of one head over one sequence of
    ``t`` positions: the entries the mask keeps, and the entries the
    score product of :func:`grouped_attention` (``window`` None) or
    :func:`window_attention` has by its shape."""
    if window is None:
        return t * (t + 1) // 2, t * t
    block, nb = window_blocks(t, window)
    return (block * (block + 1) // 2 + (t - block) * window,
            nb * block * 2 * block)


def kernel_score_entries(t: int, head_dim: int) -> int:
    """Score entries the kernels compute for one head over one causal
    sequence of ``t`` positions: the live blocks' area, at the blocks
    they run with (``ops.flash_attention.attention_blocks``)."""
    from geomx_tpu.ops.flash_attention import attention_blocks, live_blocks

    block_q, block_k = attention_blocks(t, head_dim)
    return live_blocks(t, block_q, block_k) * block_q * block_k


def kernel_window_score_entries(t: int, window: int, head_dim: int) -> int:
    """Score entries :func:`window_core` computes for one head over one
    sequence of ``t`` positions where the kernels run at that length:
    the live tiles' area at the blocks they run with for that window,
    and under :data:`KERNEL_MIN_WINDOW`, where the rule keeps the
    blocked product, that product's entries."""
    from geomx_tpu.ops.flash_attention import (attention_blocks,
                                               window_live_blocks)

    if window < KERNEL_MIN_WINDOW:
        return score_entries(t, window)[1]
    block_q, block_k = attention_blocks(t, head_dim, window)
    return window_live_blocks(t, window, block_q, block_k) \
        * block_q * block_k


def rotary_frequencies(rope, head_dim: int):
    """(inverse frequencies of the rotated pairs, float32; the factor on
    cos and sin) from one block of HF ``rope_parameters`` (a block with
    no ``partial_rotary_factor`` turns the whole head): ``default``, or
    ``yarn`` as ``_compute_yarn_parameters`` has it: pairs that turn
    more than ``beta_fast`` times over the original context keep their
    frequency, those that turn less than ``beta_slow`` times have it
    divided by ``factor``, a linear ramp over the pairs between."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    base = float(rope["rope_theta"])
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return freq.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def pair_turning(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_turning(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    scaled = freq / factor * ramp + freq * (1.0 - ramp)
    return scaled.astype(np.float32), float(rope["attention_factor"])


def rotary(x, inv_freq, factor: float, interleaved: bool = False,
           positions=None):
    """Rotary positions on the leading ``2 * len(inv_freq)`` dims of
    every head of ``x`` [B, T, ..., head_dim] (HF's half-split layout,
    ``x * cos + rotate_half(x) * sin``); the other dims pass. Angles in
    float32. ``positions`` [T]: the position id of each index along the
    axis, where that is not the index itself (two copies of a sequence
    side by side carry 0..T/2-1 twice). ``interleaved``: the pairs' members come in as neighbours
    ``(2i, 2i + 1)`` and are parted into the two halves first (HF's
    ``apply_rotary_pos_emb_interleave``); they stay parted on the way
    out, which a product of two such tensors does not see."""
    t, rot = x.shape[1], 2 * len(inv_freq)
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.float32)
    ang = jnp.asarray(positions, jnp.float32)[:, None] \
        * jnp.asarray(inv_freq)
    ang = jnp.concatenate([ang, ang], -1).reshape(
        (1, t) + (1,) * (x.ndim - 3) + (rot,))
    turned, passed = x[..., :rot].astype(jnp.float32), x[..., rot:]
    if interleaved:
        turned = jnp.concatenate([turned[..., 0::2], turned[..., 1::2]], -1)
    x1, x2 = jnp.split(turned, 2, axis=-1)
    turned = (turned * jnp.cos(ang)
              + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)) * factor
    return jnp.concatenate([turned.astype(x.dtype), passed], -1)


def rotary_attention(q, k, v, inv_freq, factor: float,
                     window: Optional[int] = None):
    """An attention branch with rotary positions on the way in: ``q``
    [B, T, KV, G, D], ``k`` and ``v`` [B, T, KV, D]. The core is
    :func:`window_core` over ``window`` keys or, with none,
    :func:`causal_core`, each in the form :func:`runs_kernel` gives. A
    dense core is computed again on the way back (``jax.checkpoint``),
    so no [T, T] scores are kept; the kernel keeps q, k, v, o and the
    log-sum-exp only, so under it a checkpoint would buy nothing and
    cost a forward kernel a pass. Returns
    ``core(rotary(q), rotary(k), v)`` [B, T, KV, G, D]
    (``models/mellum.py``)."""
    q, k = rotary(q, inv_freq, factor), rotary(k, inv_freq, factor)
    core = causal_core(q) if window is None else window_core(q, window)
    return core(q, k, v)


def causal_core(q):
    """:func:`causal_attention` as a branch keeps it: the dense product
    is computed again on the way back (``jax.checkpoint``), the kernel
    is not (:func:`rotary_attention` says why)."""
    return causal_attention if runs_kernel(q) \
        else jax.checkpoint(causal_attention)


def window_core(q, window: int):
    """Sliding-window attention with float32 scores as a branch keeps
    it, in the form :func:`runs_kernel` gives: the kernels with their
    window rule (the k sweep covers the band only and no score reaches
    HBM), or :func:`window_attention`, the blocked product, computed
    again on the way back: the CPU form, the form under a mesh, and
    what the kernels are held to. Same arithmetic in both."""
    if runs_kernel(q, window=window):
        from geomx_tpu.ops.flash_attention import flash_attention

        return partial(flash_attention, window=window)
    return jax.checkpoint(lambda q, k, v: window_attention(
        q, k, v, window, scores_dtype=jnp.float32))


def block_diffusion_mask(t: int, block: int, xp=np):
    """The [2t, 2t] mask of block-diffusion training, bool (``xp``:
    numpy, or ``jax.numpy`` to form it inside a program from two iotas
    and not as a [2t, 2t] constant): the clean copy of ``t`` positions
    before their noised copy, position x of either in block ``x //
    block``. A clean query sees the clean keys of the blocks up to and
    including its own and no noised key; a noised query the clean keys
    of the blocks strictly before its own and the noised keys of its
    own block, both directions. Every row has a live key."""
    at = xp.arange(2 * t)
    clean, blk = at < t, at % t // block
    return xp.where(
        clean[:, None], clean[None] & (blk[None] <= blk[:, None]),
        xp.where(clean[None], blk[None] < blk[:, None],
                 blk[None] == blk[:, None]))


def block_score_entries(t: int, block: int):
    """(live, computed) score entries of one head over one sequence of
    ``t`` tokens under :func:`block_diffusion_mask`: a row of either
    copy keeps as many keys as its block's end counts, ``t * (t +
    block)`` in all where blocks are whole, of the ``4 * t * t`` the
    dense product has by its shape."""
    ends = np.minimum((np.arange(t) // block + 1) * block, t)
    return 2 * int(ends.sum()), 4 * t * t


def kernel_block_score_entries(t: int, block: int, head_dim: int) -> int:
    """Score entries the kernels compute for one head over one sequence
    under the block mask: the live tiles' area, at the blocks they run
    with for ``2 * t`` positions."""
    from geomx_tpu.ops.flash_attention import (attention_blocks,
                                               block_mask_live_blocks)

    block_q, block_k = attention_blocks(2 * t, head_dim)
    return block_mask_live_blocks(t, block, block_q, block_k) \
        * block_q * block_k


def dense_block_diffusion_attention(q, k, v, block: int):
    """:func:`block_diffusion_attention` as the dense [2T, 2T] product
    under :func:`block_diffusion_mask` written out, float32 scores:
    the form off the kernels, and what the kernels are held to."""
    s = jnp.einsum("bqkgd,bjkd->bkgqj", q, k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(block_diffusion_mask(q.shape[1] // 2, block, jnp),
                  s / jnp.sqrt(jnp.float32(q.shape[-1])), -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgqj,bjkd->bqkgd", p.astype(v.dtype), v)


def block_diffusion_attention(q, k, v, block: int):
    """Attention over a clean and a noised copy of every sequence side
    by side under :func:`block_diffusion_mask`: ``q`` [B, 2T, KV, G, D]
    on ``k``, ``v`` [B, 2T, KV, D], positions already on them. ONE
    softmax a query over the keys the mask allows, float32 scores. In
    the form :func:`runs_kernel` gives, as :func:`causal_core` keeps
    it: the kernels with their block mask (dead tiles neither computed
    nor fetched), or :func:`dense_block_diffusion_attention`, computed
    again on the way back. Under the scope ``blockdiff_core``. Returns
    [B, 2T, KV, G, D] (``models/sdar.py``)."""
    with jax.named_scope("blockdiff_core"):
        if runs_kernel(q):
            from geomx_tpu.ops.flash_attention import flash_attention

            return flash_attention(q, k, v,
                                   block_mask=(q.shape[1] // 2, block))
        return jax.checkpoint(partial(
            dense_block_diffusion_attention, block=block))(q, k, v)


def latent_attention(q_nope, q_rope, k_nope, k_rope, v, inv_freq):
    """The core of latent attention (DeepSeek-V2's MLA as trained, the
    latent already expanded): a query/key head is its non-rotary part
    beside a rotary part, ``q_nope`` and ``k_nope`` [B, T, H, Dn],
    ``q_rope`` [B, T, H, Dr], and ONE rotary key ``k_rope`` [B, T, Dr]
    that every head shares; ``v`` [B, T, H, Dv] has its own size.
    Rotary positions in the interleaved pairing on all Dr dims of
    ``q_rope`` and ``k_rope``, then full causal attention over heads of
    ``Dn + Dr`` (the scale is over that size) in the form
    :func:`causal_core` gives, under the scope ``latent_core``. Returns
    [B, T, H, Dv]."""
    q_rope = rotary(q_rope, inv_freq, 1.0, interleaved=True)
    k_rope = rotary(k_rope[:, :, None], inv_freq, 1.0, interleaved=True)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], -1)
    with jax.named_scope("latent_core"):
        return causal_core(q)(q, k, v)


def gated_attention(q, k, v, gate, inv_freq, factor: float,
                    window: Optional[int] = None):
    """:func:`rotary_attention` with an element-wise sigmoid gate on the
    way out (the form Qwen3-Next publishes; ``models/laguna.py``,
    ``models/qwen3_next.py``): ``gate`` [B, T, KV * G * D] is the gate
    BEFORE its sigmoid, from the layer's own normed input. Returns
    ``rotary_attention(...) * sigmoid(gate)`` [B, T, KV * G * D], what
    the output projection takes."""
    gate = nn.sigmoid(gate)
    return rotary_attention(q, k, v, inv_freq, factor,
                            window).reshape(gate.shape) * gate


class Block(nn.Module):
    dim: int
    heads: int
    mlp_ratio: int = 4
    attn_fn: Optional[Callable] = None
    moe_experts: int = 0        # > 0: MoE FFN over the "ep" axis
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        dt = self.compute_dtype
        h = nn.LayerNorm(dtype=dt, name="ln1")(x)
        qkv = nn.Dense(3 * self.dim, dtype=dt, name="qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        hd = self.dim // self.heads
        shp = (x.shape[0], x.shape[1], self.heads, hd)
        attn = self.attn_fn or (lambda q, k, v: dense_attention(q, k, v))
        o = attn(q.reshape(shp), k.reshape(shp), v.reshape(shp))
        o = o.reshape(x.shape[0], x.shape[1], self.dim)
        x = x + nn.Dense(self.dim, dtype=dt, name="proj")(o)
        if self.moe_experts:
            from geomx_tpu.models.moe import MoEBlock

            return MoEBlock(self.dim, num_experts=self.moe_experts,
                            mlp_ratio=self.mlp_ratio, compute_dtype=dt,
                            name="moe")(x)
        h = nn.LayerNorm(dtype=dt, name="ln2")(x)
        h = nn.Dense(self.mlp_ratio * self.dim, dtype=dt, name="up")(h)
        h = nn.gelu(h)
        x = x + nn.Dense(self.dim, dtype=dt, name="down")(h)
        return x


class Transformer(nn.Module):
    vocab: int = 256
    dim: int = 128
    depth: int = 2
    heads: int = 4
    max_len: int = 2048
    attn_fn: Optional[Callable] = None
    moe_experts: int = 0        # > 0: every block's FFN is a top-1 MoE
    remat: bool = False         # rematerialize blocks (activation ckpt)
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        # tokens: [B, T] int32
        dt = self.compute_dtype
        x = nn.Embed(self.vocab, self.dim, dtype=dt, name="embed")(tokens)
        pos = nn.Embed(self.max_len, self.dim, dtype=dt, name="pos")(
            jnp.arange(tokens.shape[1])[None, :])
        x = x + pos
        # remat trades FLOPs for HBM: block activations are recomputed
        # in the backward pass instead of stored — the standard lever
        # for long sequences (jax.checkpoint under the hood)
        block_cls = nn.remat(Block) if self.remat else Block
        for i in range(self.depth):
            x = block_cls(self.dim, self.heads, attn_fn=self.attn_fn,
                          moe_experts=self.moe_experts,
                          compute_dtype=dt, name=f"block{i}")(x)
        x = nn.LayerNorm(dtype=dt, name="lnf")(x)
        return nn.Dense(self.vocab, dtype=dt, name="head")(x).astype(
            jnp.float32)


def transformer_param_sharding(mesh: Mesh):
    """Megatron-style PartitionSpec rules by parameter path suffix
    (plus expert sharding over "ep" for MoE blocks when present)."""
    has_ep = "ep" in mesh.axis_names

    def spec_for(path: str, ndim: int = 2) -> P:
        from geomx_tpu.models.moe import expert_spec, is_expert_param

        if has_ep and is_expert_param(path):
            return expert_spec(ndim)
        if path.endswith("qkv/kernel") or path.endswith("up/kernel"):
            return P(None, "tp")
        if path.endswith("qkv/bias") or path.endswith("up/bias"):
            return P("tp")
        if path.endswith("proj/kernel") or path.endswith("down/kernel"):
            return P("tp", None)
        return P()  # embeddings, norms, head, remaining biases: replicated

    def shard(params):
        def put(path_entries, leaf):
            path = "/".join(str(getattr(p, "key", p)) for p in path_entries)
            return jax.device_put(
                leaf, NamedSharding(mesh, spec_for(path, leaf.ndim)))

        return jax.tree_util.tree_map_with_path(put, params)

    return shard
