"""Device-side compression kernels (JAX/XLA).

The reference runs gradient compression as device kernels
(reference: src/kvstore/gradient_compression-inl.h:40-155 CPU kernels,
gradient_compression.cu CUDA kernels) so compression never round-trips
through host memory. This module is the TPU equivalent for the hot ops
on the WAN hop:

- ``bsc_compress``      — momentum-corrected top-k sparsification via
  ``jax.lax.top_k`` (exact, vs the reference's sampled boundary at
  gradient_compression.cc:203-233 — top-k maps directly onto the TPU
  sort unit, so sampling would save nothing and cost exactness);
- ``bsc_decompress``    — scatter back to dense;
- ``two_bit_quantize`` / ``two_bit_dequantize`` — residual-feedback
  2-bit codes packed 4/byte (reference -inl.h bitmask kernels);
- ``dgt_block_contrib`` — per-block mean |g| EWMA scoring for DGT
  channel assignment (reference: EvalMsgContribution, kv_app.h:978).

The device trainer's own selection is ``ops.select``: the same exact
top-k found by counting passes and a compaction (on a TPU a Pallas pass
over the key's rows), no sort (ROADMAP D5 moves the two ``lax.top_k``
below onto it).

All functions are pure (state in, state out) and jit-compiled per
(shape, static-arg) signature. The host-side numpy kernels in
``geomx_tpu.compression`` remain the fallback for processes without an
accelerator; ``DeviceBSCCompressor`` below adapts these kernels to the
server's Compressor interface and is selected by
``make_compressor({"device": true, ...})`` or GEOMX_DEVICE_COMPRESSION=1.

JAX is imported lazily: infra processes (schedulers, pure-CPU servers)
must not pay jax import/initialization cost unless they opt in.
"""

from __future__ import annotations

import functools
import os

import numpy as np

__all__ = [
    "bsc_compress", "bsc_decompress", "bsc_pull_compress",
    "two_bit_quantize", "two_bit_dequantize", "dgt_block_contrib",
    "DeviceBSCCompressor", "device_compression_enabled",
    "pallas_interpret", "kernel_form",
]

BSC_MOMENTUM = 0.9  # reference: gradient_compression.cc:198


def device_compression_enabled() -> bool:
    return os.environ.get("GEOMX_DEVICE_COMPRESSION", "") not in ("", "0")


def pallas_interpret() -> bool:
    """THE rule for every Pallas call site in the package (today the
    flash-attention kernels, ops/flash_attention.py, the gated delta
    rule's chain of chunks, ops/gated_delta.py, the apply's expansion,
    ops/expand.py, and the selection's compaction, ops/select.py):
    kernels are
    compiled by Mosaic when jax's default backend is a TPU and run in
    interpret mode anywhere else (the CPU test suite). Nothing else may
    choose interpret mode, so a chip run can never take it silently;
    chip_smoke.py additionally asserts the Mosaic call in the lowering."""
    import jax

    return jax.default_backend() != "tpu"


def kernel_form(operand, size: int, minimum: int, mesh=None,
                forced=None) -> bool:
    """THE predicate of the ops that are one algorithm in two forms, a
    Pallas kernel and plain XLA (``ops/expand.py``, the compaction of
    ``ops/select.py``; each states its own ``minimum``): the kernel
    where Pallas compiles (:func:`pallas_interpret` false: a TPU
    backend), no mesh is in play (a Pallas call has no partitioning
    rule; seen as ``models.transformer.runs_kernel`` sees it, the
    abstract mesh of the context and of the operand's own sharding, or
    handed over as ``mesh`` by a caller whose operands GSPMD shards over
    ``Auto`` axes, which a trace does not show) and ``size`` is at least
    ``minimum``; XLA's form otherwise. It reads what a trace can see and
    nothing names a model. ``operand`` is an array, a tracer or a
    ``ShapeDtypeStruct``. ``forced`` is for tests: the answer itself."""
    if forced is not None:
        return forced
    import jax

    return (not pallas_interpret()
            and mesh is None
            and jax.sharding.get_abstract_mesh().empty
            and jax.typeof(operand).sharding.mesh.empty
            and size >= minimum)


# ---------------------------------------------------------------------------
# jitted kernels (built lazily, cached per static signature)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _bsc_compress_fn(k: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(grad, u, v):
        u = BSC_MOMENTUM * u + grad
        v = v + u
        mags, idx = jax.lax.top_k(jnp.abs(v), k)
        vals = v[idx]
        v = v.at[idx].set(0.0)
        u = u.at[idx].set(0.0)
        return vals, idx.astype(jnp.int32), u, v

    return fn


def bsc_compress(grad, u, v, threshold: float):
    """Momentum-corrected EXACT top-k selection on device.

    Returns ``(values, indices, new_u, new_v)`` — functional counterpart
    of the reference's in-place BSCompress (gradient_compression.cc:191).
    """
    k = max(int(grad.size * threshold), 1)
    return _bsc_compress_fn(k)(grad, u, v)


@functools.lru_cache(maxsize=None)
def _bsc_decompress_fn(n: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(values, indices):
        return jnp.zeros(n, jnp.float32).at[indices].set(values)

    return fn


def bsc_decompress(values, indices, original_size: int):
    """Scatter-back (reference: BSCDecompress :310-336)."""
    return _bsc_decompress_fn(original_size)(values, indices)


@functools.lru_cache(maxsize=None)
def _bsc_pull_fn(cap: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(arr):
        # the reference's non-zero filter (BSCPullCompress :271-308):
        # top-|value| selection is equivalent on an aggregate whose
        # nonzeros number <= cap, and degrades gracefully past cap
        mags, idx = jax.lax.top_k(jnp.abs(arr), cap)
        return arr[idx], idx.astype(jnp.int32)

    return fn


def bsc_pull_compress(arr, threshold: float, multiplier: int):
    cap = max(min(int(arr.size * threshold * multiplier), arr.size), 1)
    return _bsc_pull_fn(cap)(arr)


@functools.lru_cache(maxsize=None)
def _two_bit_fn(n: int):
    import jax
    import jax.numpy as jnp

    pad = (-n) % 4

    def pack(codes):
        c = codes.reshape(-1, 4).astype(jnp.uint8)
        return c[:, 0] | (c[:, 1] << 2) | (c[:, 2] << 4) | (c[:, 3] << 6)

    @jax.jit
    def fn(grad, residual, threshold):
        r = residual + grad
        pos = r > threshold
        neg = r < -threshold
        codes = jnp.where(pos, 1, jnp.where(neg, 2, 0)).astype(jnp.uint8)
        r = jnp.where(pos, r - threshold, jnp.where(neg, r + threshold, r))
        if pad:
            codes = jnp.concatenate(
                [codes, jnp.zeros(pad, jnp.uint8)])
        return pack(codes), r

    return fn


def two_bit_quantize(grad, residual, threshold: float):
    """Residual-feedback 2-bit quantization, 4 codes/byte.

    Returns ``(packed_uint8, new_residual)``."""
    import jax.numpy as jnp

    return _two_bit_fn(int(grad.size))(grad, residual,
                                       jnp.float32(threshold))


@functools.lru_cache(maxsize=None)
def _two_bit_deq_fn(n: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(packed, threshold):
        c = jnp.stack([packed & 3, (packed >> 2) & 3,
                       (packed >> 4) & 3, (packed >> 6) & 3],
                      axis=1).reshape(-1)[:n]
        return jnp.where(c == 1, threshold,
                         jnp.where(c == 2, -threshold, 0.0)
                         ).astype(jnp.float32)

    return fn


def two_bit_dequantize(packed, original_size: int, threshold: float):
    import jax.numpy as jnp

    return _two_bit_deq_fn(int(original_size))(packed,
                                               jnp.float32(threshold))


@functools.lru_cache(maxsize=None)
def _dgt_contrib_fn(n: int, block_size: int, alpha: float):
    import jax
    import jax.numpy as jnp

    nblocks = -(-n // block_size)
    pad = nblocks * block_size - n

    @jax.jit
    def fn(grad, prev):
        g = jnp.abs(grad)
        if pad:
            g = jnp.concatenate([g, jnp.zeros(pad, g.dtype)])
        # padded tail block: mean over true elements
        sums = g.reshape(nblocks, block_size).sum(axis=1)
        counts = jnp.full((nblocks,), block_size, jnp.float32)
        if pad:
            counts = counts.at[-1].set(block_size - pad)
        cur = sums / counts
        return alpha * prev + (1.0 - alpha) * cur

    return fn


def dgt_block_contrib(grad, prev, block_size: int, alpha: float):
    """EWMA per-block mean |g| (reference: EvalMsgContribution,
    kv_app.h:978) — the DGT channel-assignment score, on device."""
    return _dgt_contrib_fn(int(grad.size), int(block_size),
                           float(alpha))(grad, prev)


# ---------------------------------------------------------------------------
# server-side adapter
# ---------------------------------------------------------------------------

def _host():
    """geomx_tpu.compression via sys.modules: these methods run in server
    handler threads, where a function-local geomx_tpu import can deadlock
    on the package import lock (compression is guaranteed imported — it
    is the only constructor of DeviceBSCCompressor)."""
    import sys

    return sys.modules["geomx_tpu.compression"]


_base_compressor = None


def _host_base():
    global _base_compressor
    if _base_compressor is None:
        _base_compressor = _host().Compressor()
    return _base_compressor



class DeviceBSCCompressor:
    """Drop-in for compression.BSCCompressor with device state/kernels.

    Per-key momentum (u) and accumulation (v) stay resident on the
    accelerator; only the compressed (values, indices) pair crosses to
    host for the wire (device-vs-host pack throughput on the chip: not
    measured).
    """

    type_name = "bsc"

    def __init__(self, threshold: float = 0.01):
        self.threshold = threshold
        self._u = {}
        self._v = {}

    def compress_push(self, arr, state_key=None):
        import jax.numpy as jnp

        a = jnp.asarray(np.asarray(arr, dtype=np.float32))
        if state_key not in self._u:
            self._u[state_key] = jnp.zeros(a.size, jnp.float32)
            self._v[state_key] = jnp.zeros(a.size, jnp.float32)
        vals, idx, self._u[state_key], self._v[state_key] = bsc_compress(
            a, self._u[state_key], self._v[state_key], self.threshold)
        return (np.asarray(vals, dtype=np.float32),
                np.asarray(idx, dtype=np.int32), "bsc")

    def decompress_push(self, tag, val, aux, orig_len):
        if tag == "bsc" and orig_len >= 1 << 16:
            return np.asarray(bsc_decompress(
                np.asarray(val, np.float32),
                np.asarray(_host().plain_positions(aux), np.int32),
                orig_len))
        return _host()._generic_decompress(tag, val, aux, orig_len)

    def compress_pull(self, tag, arr, factor):
        if tag != "bsc":
            return _host_base().compress_pull(tag, arr, factor)
        vals, idx = bsc_pull_compress(
            np.asarray(arr, dtype=np.float32), self.threshold, factor)
        return (np.asarray(vals, dtype=np.float32),
                np.asarray(idx, dtype=np.int32))

    def decompress_pull(self, tag, val, aux, orig_len, factor):
        return self.decompress_push(tag, val, aux, orig_len)

    def pull_compr_tag(self, num_elems: int = 0) -> str:
        return "bsc"

    def push_tag(self, num_elems: int = 0) -> str:
        return "bsc"
