"""Quantized ring all-reduce inside the jitted step (EQuARX proper).

PR 8 made the mesh tier's intra-party aggregation a full-precision
GSPMD psum; PR 10 quantized the host wire. This module fuses the two:
an explicit ``shard_map`` + ``ppermute`` ring (reduce-scatter, then
all-gather) where every hop's chunk is quantized ON DEVICE before it
crosses the link — block-scaled int8 by default (EQuARX's scheme),
2-bit error-feedback and fp16 as alternate policies, all reusing the
:mod:`geomx_tpu.compression.device` / :mod:`geomx_tpu.ops` kernels.
Selected by ``GEOMX_MESH_CODEC``; ``"none"`` keeps the PR-8 psum
byte-for-byte (callers bypass this module entirely).

Ring schedule (P ranks, vector padded to P chunks of m elements):

- **reduce-scatter** (P-1 hops): at step s, rank r quantizes its
  running partial for chunk ``(r - s) % P`` and sends it to rank r+1;
  the receiver dequantizes and adds its own copy of the next chunk.
  After P-1 steps rank r owns chunk ``(r + 1) % P`` fully summed.
- **all-gather** (P-1 hops): the owner quantizes its finished chunk
  ONCE; every later hop relays the codes VERBATIM. All ranks — the
  owner included — dequantize the same bytes, so replicas are
  bit-identical by construction (no per-hop requantization noise, and
  nothing for ``check_vma`` to distrust).

Error feedback: each rank carries a ``(P, m)`` residual — slots
``0..P-2`` feed the reduce-scatter steps, slot ``P-1`` the all-gather
origin quantize. The step->chunk mapping is fixed (slot s always
covers chunk ``(r - s) % P``), so each slot tracks one chunk's error
stream across rounds and repeated rounds stay convergent. Residuals
are threaded through the jitted step explicitly (state in, state out)
— nothing here touches host memory inside the step.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from geomx_tpu.parallel.mesh import P, ring_chunk_layout, ring_perm

__all__ = ["RING_SLOTS", "ring_all_reduce", "residual_slots",
           "make_quant_all_reduce", "QuantRingReducer", "ring_wire_bytes"]


def _jax():
    import jax

    return jax


def _device():
    from geomx_tpu.compression import device

    return device


def residual_slots(size: int) -> int:
    """Residual slots per rank: P-1 reduce-scatter steps + 1 all-gather
    origin quantize."""
    return max(1, int(size))


RING_SLOTS = residual_slots


def _codec_multiple(codec: str, block: int) -> int:
    """Chunk-size granularity the codec packs at."""
    if codec == "int8":
        return max(1, int(block))
    if codec == "2bit":
        return 4
    return 1


class _HopCodec:
    """Per-hop quantize/dequantize pair for one chunk shape ``(m,)``.

    ``quantize`` returns ``(wire, deq, new_residual)`` where ``wire``
    is the tuple of arrays a hop actually moves (codes + sidecar) and
    ``deq`` is the receiver-identical dequantized value; ``dequantize``
    recovers ``deq`` from ``wire`` alone. Pure traced functions — safe
    inside shard_map.
    """

    def __init__(self, codec: str, m: int, block: int, threshold: float):
        self.codec = codec
        self.m = int(m)
        self.block = max(1, int(block))
        self.threshold = float(threshold)

    def quantize(self, partial, res_slot):
        jnp = _jax().numpy
        if self.codec == "2bit":
            from geomx_tpu import ops

            packed, new_res = ops.two_bit_quantize(
                partial, res_slot, self.threshold)
            return (packed,), self.dequantize((packed,)), new_res
        e = partial + res_slot
        if self.codec == "int8":
            dev = _device()
            codes, scales = dev.block_quant_int8(e, self.block)
            deq = dev.block_dequant_int8(codes, scales, self.block)
            return (codes, scales), deq, e - deq
        if self.codec == "fp16":
            half = e.astype(jnp.float16)
            deq = half.astype(jnp.float32)
            return (half,), deq, e - deq
        raise ValueError(f"unknown mesh codec {self.codec!r}")

    def dequantize(self, wire):
        jnp = _jax().numpy
        if self.codec == "2bit":
            from geomx_tpu import ops

            return ops.two_bit_dequantize(wire[0], self.m, self.threshold)
        if self.codec == "int8":
            return _device().block_dequant_int8(wire[0], wire[1],
                                                self.block)
        if self.codec == "fp16":
            return wire[0].astype(jnp.float32)
        raise ValueError(f"unknown mesh codec {self.codec!r}")


def ring_all_reduce(x, residual, *, size: int, axis_name: str = "dp",
                    codec: str = "int8", block: int = 256,
                    threshold: float = 0.5) -> Tuple:
    """Quantized ring all-reduce of this rank's flat f32 vector ``x``.

    Call INSIDE shard_map over ``axis_name`` (``size`` ranks). Every
    rank passes its own ``(n,)`` contribution and its ``(P, m)``
    residual slice; returns ``(summed (n,), new_residual (P, m))``
    with the sum bit-identical on every rank. ``codec="none"`` is the
    caller's branch (keep the psum path) — rejected here.
    """
    jax = _jax()
    jnp = jax.numpy
    lax = jax.lax
    if codec not in ("int8", "2bit", "fp16"):
        raise ValueError(
            f"ring_all_reduce: codec {codec!r} not in ('int8', '2bit', "
            "'fp16'); 'none' keeps the psum path at the call site")
    size = int(size)
    n = int(x.size)
    m, padded = ring_chunk_layout(n, size, _codec_multiple(codec, block))
    hop = _HopCodec(codec, m, block, threshold)
    perm = ring_perm(size)

    xp = jnp.zeros(padded, jnp.float32).at[:n].set(
        jnp.asarray(x, jnp.float32).ravel())
    chunks = xp.reshape(size, m)
    r = lax.axis_index(axis_name)

    def hop_send(wire):
        return tuple(lax.ppermute(w, axis_name, perm) for w in wire)

    new_res = []
    # reduce-scatter: quantize the running partial every hop
    send_val = jnp.take(chunks, r, axis=0)
    for s in range(size - 1):
        wire, _deq, res_s = hop.quantize(send_val, residual[s])
        new_res.append(res_s)
        rx = hop_send(wire)
        send_val = hop.dequantize(rx) + jnp.take(chunks,
                                                 (r - s - 1) % size, axis=0)
    # send_val is now chunk (r+1) % size, fully summed on this rank
    wire, own_deq, res_ag = hop.quantize(send_val, residual[size - 1])
    new_res.append(res_ag)

    # all-gather: relay the owner's codes verbatim; every rank (owner
    # included) dequantizes the same bytes -> bit-identical replicas
    out = jnp.zeros((size, m), jnp.float32)
    out = out.at[(r + 1) % size].set(own_deq)
    cur = wire
    for t in range(size - 1):
        cur = hop_send(cur)
        out = out.at[(r - t) % size].set(hop.dequantize(cur))

    return out.reshape(-1)[:n], jnp.stack(new_res)


def ring_wire_bytes(codec: str, n: int, size: int, block: int = 256) -> int:
    """Link bytes the quantized ring moves per all-reduce, in the same
    ``2 * (P - 1) * wire_bytes`` model PR 8 used for the fp32 psum —
    codes + sidecar scales/threshold per hop, summed over both phases.
    """
    size = int(size)
    if size <= 1:
        return 0
    dev = _device()
    if codec in ("none", ""):
        return 2 * (size - 1) * 4 * int(n)
    m, _ = ring_chunk_layout(int(n), size, _codec_multiple(codec, block))
    return 2 * (size - 1) * size * dev.mesh_wire_bytes(codec, m, block)


def zero_residual(size: int, n: int, codec: str, block: int = 256):
    """Global error-feedback state for one ring: ``(P, P, m)`` zeros,
    to be sharded ``P(axis_name)`` on the leading (rank) axis."""
    m, _ = ring_chunk_layout(int(n), int(size),
                             _codec_multiple(codec, block))
    return np.zeros((int(size), residual_slots(size), m), np.float32)


def make_quant_all_reduce(mesh, codec: str, n: int, *,
                          axis_name: str = "dp", block: int = 256,
                          threshold: float = 0.5, mean: bool = False):
    """Jitted standalone quantized all-reduce over ``mesh``.

    Returns ``fn(x_stacked, residual) -> (reduced, new_residual)``:
    ``x_stacked`` is ``(P, n)`` (rank r's contribution in row r, to be
    sharded ``P(axis_name)``), ``residual`` the ``zero_residual``
    array. ``reduced`` is the replicated ``(n,)`` sum (mean when
    ``mean=True``). ``codec="none"`` degrades to a plain psum with a
    pass-through residual — the reference the quantized paths are
    measured against.
    """
    jax = _jax()
    size = int(mesh.shape[axis_name])

    if codec == "none":
        def body(xs, res):
            y = jax.lax.psum(xs[0], axis_name)
            return (y / size if mean else y), res

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(axis_name), P(axis_name)),
                           out_specs=(P(), P(axis_name)),
                           check_vma=False)
        return jax.jit(fn)

    def body(xs, res):
        y, new_res = ring_all_reduce(
            xs[0], res[0], size=size, axis_name=axis_name, codec=codec,
            block=block, threshold=threshold)
        return (y / size if mean else y), new_res[None]

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis_name), P(axis_name)),
                       out_specs=(P(), P(axis_name)), check_vma=False)
    return jax.jit(fn)


class QuantRingReducer:
    """Stateful wrapper: one quantized all-reduce per round for one
    fixed vector size, holding the (device-resident) residual between
    rounds. This is the unit ``KVStorePartyMesh`` hands the trainers —
    one per gradient key, so residual streams never mix across keys.
    """

    def __init__(self, mesh, codec: str, n: int, *,
                 axis_name: str = "dp", block: int = 256,
                 threshold: float = 0.5, mean: bool = False):
        dev = _device()
        if codec not in dev.MESH_CODECS:
            raise ValueError(
                f"GEOMX_MESH_CODEC={codec!r}: expected one of "
                f"{dev.MESH_CODECS}")
        self.mesh = mesh
        self.codec = codec
        self.n = int(n)
        self.block = int(block)
        self.mean = bool(mean)
        self.size = int(mesh.shape[axis_name])
        self._axis = axis_name
        self._fn = make_quant_all_reduce(
            mesh, codec, self.n, axis_name=axis_name, block=block,
            threshold=threshold, mean=mean)
        self._res = self._zero()

    def _zero(self):
        jax = _jax()
        from jax.sharding import NamedSharding

        host = zero_residual(self.size, self.n, self.codec, self.block) \
            if self.codec != "none" else np.zeros(
                (self.size, 1, 1), np.float32)
        return jax.device_put(
            host, NamedSharding(self.mesh, P(self._axis)))

    def reduce(self, x_stacked):
        """All-reduce ``(P, n)`` -> replicated ``(n,)``, advancing the
        residual stream by one round."""
        y, self._res = self._fn(x_stacked, self._res)
        return y

    def reset(self) -> None:
        """Zero the residual streams — abort/membership recovery
        re-seeds rather than replaying stale error (same policy as
        ``WireCodec.reset``)."""
        self._res = self._zero()

    def wire_bytes_per_round(self) -> int:
        return ring_wire_bytes(self.codec, self.n, self.size, self.block)
