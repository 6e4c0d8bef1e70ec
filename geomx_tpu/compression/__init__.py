"""WAN gradient compression: FP16, Bi-Sparse (BSC), 2-bit, MPQ.

Re-implements the reference's GradientCompression family (reference:
src/kvstore/gradient_compression.cc:40-336, kernels
gradient_compression-inl.h:40-155) as host-side numpy kernels used on the
inter-DC hop by the HiPS server. Device (JAX/XLA + Pallas) versions live
in ``geomx_tpu.ops``; ``make_compressor({"type": "bsc", "device": true})``
or GEOMX_DEVICE_COMPRESSION=1 routes the server's WAN hop through them —
for multi-million-element keys the device top-k dominates the host
partition (4.9-9.2x at 8M elements on a v5e, measured against the
permutation-based host pass, whose shuffle of all n positions was most
of its cost; not re-measured since the sample is drawn in O(sample)).
Placement matches the reference: the
LAN tier is uncompressed; party servers compress the aggregated gradient
before the WAN push (BSCompress, :191), the global server decompresses,
aggregates, and compresses pull responses with the non-zero filter scaled
by the number of global workers (BSCPullCompress, :271). Where every push
of a round is Bi-Sparse and the servers only aggregate, nothing is
decompressed at all: a worker's selection reaches the party server's
Bi-Sparse state as ``entries.Pairs`` (positions and values as the wire
has them; ``bsc_compress`` adds them into ``u`` where they are), and the
round's aggregate is ``entries.Entries`` (sorted positions, float32
values) from the global server's sum to the party server's ack, the
response its exact non-zero set.

Wire-format divergence from the reference (documented, intentional): the
reference pads compressed buffers to a fixed size with the placeholder
value -65530 and index -1 and smuggles the original size through a second
wire key (kvstore_dist_server.h:1479-1483); our messages carry explicit
(values, indices) arrays of exact length plus (offset,total,len) meta, so
no placeholders are needed.

Compression tags travel in ``Meta.compr`` / ``KVPairs.compr``:
"" (none), "fp16", "bsc", "2bit" — plus "bsc16" (BSC with float16
values) on the quantized combined wire (``compression.device``). A tag
names how the VALUES travel. The positions of a ``bsc`` / ``bsc16``
payload are int32 on the LAN; on the party-global link, where they
ascend strictly, they are the code of their gaps, a ``uint8`` part
(``entries.CODED``: a third of their bytes at 1%), which
``Pairs.from_wire`` / ``Entries.from_wire`` decode.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple, Union

import numpy as np

from geomx_tpu import kernels_native
from geomx_tpu.compression.entries import (Entries, Pairs, SPARSE_TAGS,
                                            plain_positions)

__all__ = ["make_compressor", "Compressor", "FP16Compressor", "BSCCompressor",
           "TwoBitCompressor", "MPQCompressor", "bsc_compress", "bsc_decompress",
           "bsc_pull_compress", "two_bit_quantize", "two_bit_dequantize",
           "takes_pairs", "draw_ahead", "Entries", "Pairs", "SPARSE_TAGS",
           "plain_positions"]

BSC_MOMENTUM = 0.9  # reference: gradient_compression.cc:198


def _ops():
    """geomx_tpu.ops via sys.modules-or-import. make_compressor runs in
    SERVER HANDLER THREADS (SET_GRADIENT_COMPRESSION command) while the
    server's main thread may be blocked inside ``import geomx_tpu``; a
    plain function-local import would deadlock on the package import
    lock, so resolve from sys.modules first (geomx_tpu/__init__ imports
    ops eagerly)."""
    import sys

    mod = sys.modules.get("geomx_tpu.ops")
    if mod is not None:
        return mod
    from geomx_tpu import ops

    return ops


# ---------------------------------------------------------------------------
# stateless kernels
# ---------------------------------------------------------------------------

def _bsc_sample_size(n: int, threshold: float) -> int:
    """0.5% of n, at least ceil(10/threshold) entries, never more than n
    (reference: :203-212)."""
    size = int(n * 0.005) if n * 0.005 * threshold >= 10 \
        else int(np.ceil(10 / threshold))
    return min(max(size, 1), n)


def bsc_sample_positions(n: int, threshold: float,
                         rng: np.random.Generator) -> np.ndarray:
    """The positions of the boundary sample: uniform over range(n),
    without replacement, drawn in O(sample) (Floyd's algorithm inside
    ``Generator.choice``; a permutation of all n positions cost 84% of
    the pass at 38.6M elements). All positions when the sample is n."""
    size = _bsc_sample_size(n, threshold)
    if size >= n:
        return np.arange(n)
    return rng.choice(n, size, replace=False, shuffle=False)


def _boundary_of(sample: np.ndarray, threshold: float) -> float:
    """The smallest of the ``threshold`` largest of ``sample`` (>= 0)."""
    top_k = min(max(int(sample.size * threshold), 1), sample.size)
    return float(np.partition(sample, -top_k)[-top_k])


def bsc_sample_boundary(v: np.ndarray, threshold: float,
                        rng: np.random.Generator,
                        positions: Optional[np.ndarray] = None) -> float:
    """Top-k boundary from a random 0.5% sample (reference: :203-233).
    ``positions`` are those of :func:`bsc_sample_positions`, for a caller
    that draws them itself (under its lock on a shared ``rng``)."""
    if positions is None:
        positions = bsc_sample_positions(v.size, threshold, rng)
    return _boundary_of(np.abs(v[positions]), threshold)


# elements per block of the selection pass: 1 MB of float32, so the
# |v| and mask temporaries stay in cache and none is O(n)
_SELECT_BLOCK = 1 << 18


def _select_at_least(v: np.ndarray, boundary: float, cap: int) -> np.ndarray:
    """The first ``cap`` positions, in index order, with |v| >= boundary
    (``np.nonzero(np.abs(v) >= boundary)[0][:cap]`` block by block,
    stopping at the cap)."""
    found, count = [], 0
    mag = np.empty(min(_SELECT_BLOCK, v.size), dtype=v.dtype)
    for lo in range(0, v.size, _SELECT_BLOCK):
        block = v[lo:lo + _SELECT_BLOCK]
        m = np.abs(block, out=mag[:block.size])
        hit = np.nonzero(m >= boundary)[0]
        if hit.size:
            hit += lo
            found.append(hit)
            count += hit.size
            if count >= cap:
                break
    if not found:
        return np.empty(0, dtype=np.intp)
    return np.concatenate(found)[:cap]


def bsc_compress(grad: Union[np.ndarray, Pairs], u: np.ndarray,
                 v: np.ndarray, threshold: float,
                 rng: Optional[np.random.Generator] = None,
                 positions: Optional[np.ndarray] = None,
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Momentum-corrected top-k sparsification (reference: :191-268).

    Mutates ``u``/``v`` in place (momentum correction + residual reset for
    the transmitted coordinates); reads ``grad`` only. Returns (values,
    indices). ``positions``: see :func:`bsc_sample_boundary`.

    ``grad`` is an array or, sparse, :class:`Pairs` over as many
    elements: its values are then added into ``u`` where they are. Every
    element sees the same float32 operations in the same order either
    way (``0.9 * u + g``, then ``v + u``), so the selection, ``u`` and
    ``v`` are equal (an untouched ``-0.0`` of ``u`` keeps the sign that
    ``+ 0.0`` clears; a position repeated inside one payload adds its
    values to ``u`` one by one, not their sum).
    """
    n = grad.size
    zipped = max(int(n * threshold), 1)
    if positions is None:
        if rng is None:     # the reference uses a fixed seed (:212)
            rng = np.random.default_rng(42)
        positions = bsc_sample_positions(n, threshold, rng)
    if isinstance(grad, Pairs) and u.size == n \
            and kernels_native.bsc_pass_usable(u, v):
        swept = _one_sweep(grad, u, v, threshold, positions, zipped)
        if swept is not None:
            return swept
    u *= BSC_MOMENTUM
    if isinstance(grad, Pairs):
        grad.add_into(u)
    else:
        u += grad
    v += u
    boundary = bsc_sample_boundary(v, threshold, rng, positions)
    selected = _select_at_least(v, boundary, zipped)
    values = v[selected]
    v[selected] = 0.0
    u[selected] = 0.0
    return values.astype(np.float32, copy=False), selected.astype(np.int32)


def _one_sweep(grad: Pairs, u: np.ndarray, v: np.ndarray, threshold: float,
               positions: np.ndarray, cap: int,
               ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """:func:`bsc_compress` of pairs as one sweep over ``u`` and ``v``
    (``kernels_native``: 16 bytes an element moved where the numpy
    passes move 24 and then gather and scatter the selection): the
    boundary is read ahead, from what the sampled positions are about
    to hold, so a block is decayed, accumulated, compared and cleared
    while it is in cache. The same float32 operations in the same order
    an element, the same sample, so the same selection, ``u`` and ``v``.
    None, nothing touched, for pairs outside the key (numpy's error is
    the passes' to raise)."""
    idx = grad.idx.astype(np.int64, copy=False)
    vals = np.ascontiguousarray(grad.vals, dtype=np.float32)
    if idx.size > 1 and not (idx[1:] >= idx[:-1]).all():
        order = np.argsort(idx, kind="stable")  # equal positions keep
        idx, vals = idx[order], vals[order]     # their order of arrival
    if idx.size and not (idx[0] >= 0 and idx[-1] < u.size):
        return None
    idx = np.ascontiguousarray(idx)
    pos = np.sort(positions).astype(np.int64, copy=False)
    boundary = _boundary_of(kernels_native.bsc_sample(
        u, v, BSC_MOMENTUM, idx, vals, pos), threshold)
    return kernels_native.bsc_sweep(u, v, BSC_MOMENTUM, idx, vals, boundary,
                                    cap)


def bsc_pull_compress(arr: np.ndarray, threshold: float, multiplier: int,
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Non-zero filter for pull responses, capacity scaled by the number of
    contributing global workers (reference: BSCPullCompress :271-308)."""
    cap = max(int(arr.size * threshold * multiplier), 1)
    idx = np.nonzero(arr)[0][:cap]
    return arr[idx].astype(np.float32), idx.astype(np.int32)


def bsc_decompress(values: np.ndarray, indices: np.ndarray,
                   original_size: int) -> np.ndarray:
    """Scatter back to dense (reference: BSCDecompress :310-336)."""
    out = np.zeros(original_size, dtype=np.float32)
    valid = indices >= 0
    out[indices[valid]] = values[valid]
    return out


def two_bit_quantize(grad: np.ndarray, residual: np.ndarray, threshold: float,
                     ) -> np.ndarray:
    """2-bit quantization with residual feedback (reference kernels:
    gradient_compression-inl.h:40-155). Packs 4 codes per byte:
    0 = zero, 1 = +threshold, 2 = -threshold."""
    residual += grad
    pos = residual > threshold
    neg = residual < -threshold
    codes = np.zeros(grad.size, dtype=np.uint8)
    codes[pos] = 1
    codes[neg] = 2
    residual[pos] -= threshold
    residual[neg] += threshold
    pad = (-grad.size) % 4
    if pad:
        codes = np.concatenate([codes, np.zeros(pad, np.uint8)])
    c = codes.reshape(-1, 4)
    packed = c[:, 0] | (c[:, 1] << 2) | (c[:, 2] << 4) | (c[:, 3] << 6)
    return packed.astype(np.uint8)


def two_bit_dequantize(packed: np.ndarray, original_size: int,
                       threshold: float) -> np.ndarray:
    codes = np.empty((packed.size, 4), dtype=np.uint8)
    codes[:, 0] = packed & 3
    codes[:, 1] = (packed >> 2) & 3
    codes[:, 2] = (packed >> 4) & 3
    codes[:, 3] = (packed >> 6) & 3
    flat = codes.reshape(-1)[:original_size]
    out = np.zeros(original_size, dtype=np.float32)
    out[flat == 1] = threshold
    out[flat == 2] = -threshold
    return out


# ---------------------------------------------------------------------------
# compressor objects (server-side dispatch)
# ---------------------------------------------------------------------------

class Compressor:
    """No-op compressor (CompressionType::kNone)."""

    type_name = "none"

    def compress_push(self, arr: np.ndarray, state_key=None):
        """-> (wire_values, aux_or_None, tag). ``arr`` is an array:
        only a compressor that :func:`takes_pairs` (the host Bi-Sparse
        pass) may be handed a sparse gradient instead."""
        return arr, None, ""

    def decompress_push(self, tag: str, val: np.ndarray,
                        aux: Optional[np.ndarray], orig_len: int) -> np.ndarray:
        """-> the dense push. Either ``val`` itself or a new array that
        nothing else holds: the server keeps one that owns its data as
        the round's accumulator without copying it.

        This is the path of a push whose receiver needs every element:
        the dense wires, and a ``bsc`` / ``bsc16`` push to a server that
        applies it or forwards an array (single tier, HFA, TSEngine, a
        compressor that does not :func:`takes_pairs`). Neither store
        calls it for a Bi-Sparse push it can keep sparse: a GLOBAL store
        takes the payload as :class:`Entries` (``Entries.from_wire``)
        and sums index lists, a party server that re-selects with
        Bi-Sparse takes it as :class:`Pairs` and hands them to
        ``compress_push``; both keep the wire's own arrays, and a store
        is dense again only where someone asks it for an array."""
        return _generic_decompress(tag, val, aux, orig_len)

    def compress_pull(self, tag: str, arr: np.ndarray, factor: int):
        """-> (wire_values, aux_or_None) for a pull response."""
        if tag == "fp16":
            return arr.astype(np.float16), None
        return arr, None

    def decompress_pull(self, tag: str, val: np.ndarray,
                        aux: Optional[np.ndarray], orig_len: int,
                        factor: int) -> np.ndarray:
        return _generic_decompress(tag, val, aux, orig_len)

    def pull_compr_tag(self, num_elems: int = 0) -> str:
        return ""

    def push_tag(self, num_elems: int = 0) -> str:
        return ""


def _generic_decompress(tag, val, aux, orig_len):
    if tag == "" or tag is None:
        return val
    if tag == "fp16":
        return val.astype(np.float32)
    if tag == "rsp":
        # row-sparse push (reference: EncodeRowSparseKey,
        # kvstore_dist.h:906): aux = row ids, val = those rows flattened;
        # scatter-ADD into a dense delta so overlapping rows from
        # different workers aggregate by sum
        ids = np.asarray(aux, dtype=np.int64).ravel()
        out = np.zeros(orig_len, dtype=np.float32)
        if ids.size:
            rows = np.asarray(val, dtype=np.float32).reshape(ids.size, -1)
            row_len = rows.shape[1]
            n_rows = orig_len // row_len
            ok = (ids >= 0) & (ids < n_rows)
            if not ok.all():
                import logging

                logging.getLogger("geomx.compression").warning(
                    "row-sparse push: dropping %d out-of-range row ids "
                    "(key has %d rows)", int((~ok).sum()), n_rows)
                ids, rows = ids[ok], rows[ok]
            np.add.at(out.reshape(n_rows, row_len), ids, rows)
        return out
    if tag in SPARSE_TAGS:
        # scatter-ADD, not assignment: a push payload carrying duplicate
        # indices must aggregate by sum (same contract as the "rsp"
        # branch above); for pull payloads indices are unique (nonzeros
        # of one array) so add and set coincide. "bsc16" is the same
        # wire with float16 values (quantized combined wire) — they
        # widen on arrival and aggregation stays fp32
        return Pairs.from_wire(val, aux, orig_len).dense()
    if tag == "2bit":
        assert aux is not None and aux.size == 1, "2bit payload missing threshold"
        return two_bit_dequantize(val, orig_len, float(aux[0]))
    raise ValueError(f"unknown compression tag {tag!r}")


class FP16Compressor(Compressor):
    """Low-precision FP16 transmission (the reference achieves this by
    casting the model to float16, examples/cnn_fp16.py; as a server-side
    compressor we cast on the WAN wire only, keeping fp32 aggregation)."""

    type_name = "fp16"

    def compress_push(self, arr, state_key=None):
        return arr.astype(np.float16), None, "fp16"

    def pull_compr_tag(self, num_elems: int = 0) -> str:
        return "fp16"

    def push_tag(self, num_elems: int = 0) -> str:
        return "fp16"


class BSCCompressor(Compressor):
    """Bi-Sparse Compression with per-key momentum/residual state."""

    type_name = "bsc"

    def __init__(self, threshold: float = 0.01):
        self.threshold = threshold
        self._u: Dict = {}
        self._v: Dict = {}
        self._rng = np.random.default_rng(42)
        # the boundary-sampling Generator is shared across keys, and
        # per-key-locked server threads compress different keys
        # concurrently; numpy Generators are not thread-safe
        self._rng_lock = __import__("threading").Lock()

    def draw(self, num_elems: int, state_key=None) -> np.ndarray:
        """What ``compress_push`` of a key of ``num_elems`` elements
        shares with the other keys, done apart from it: the key's state
        allocated on first sight, and the boundary sample's positions
        out of the one generator. A caller that compresses the keys of a
        batch side by side calls this for each of them first, in the
        batch's order, from one thread (the generator's stream is then
        the serial pass's), and hands each key's positions on."""
        if state_key not in self._u:
            self._u[state_key] = np.zeros(num_elems, dtype=np.float32)
            self._v[state_key] = np.zeros(num_elems, dtype=np.float32)
        with self._rng_lock:
            return bsc_sample_positions(num_elems, self.threshold, self._rng)

    def compress_push(self, arr, state_key=None, positions=None):
        """``arr``: the gradient as an array or as :class:`Pairs` (a
        party server's aggregate of Bi-Sparse pushes, never made
        dense); the state, the draw and the selection are the same.
        ``positions``: those of :meth:`draw` for this key, where the
        caller drew ahead; the rest touches this key's state alone."""
        if positions is None:
            positions = self.draw(arr.size, state_key)
        if not isinstance(arr, Pairs):
            arr = np.asarray(arr, dtype=np.float32)
        values, indices = bsc_compress(
            arr, self._u[state_key], self._v[state_key], self.threshold,
            positions=positions)
        return values, indices, "bsc"

    def compress_pull(self, tag, arr, factor):
        if tag != "bsc":
            return super().compress_pull(tag, arr, factor)
        values, indices = bsc_pull_compress(
            np.asarray(arr, dtype=np.float32), self.threshold, factor)
        return values, indices

    def pull_compr_tag(self, num_elems: int = 0) -> str:
        return "bsc"

    def push_tag(self, num_elems: int = 0) -> str:
        return "bsc"


class TwoBitCompressor(Compressor):
    """Legacy 2-bit quantization with residual feedback."""

    type_name = "2bit"

    def __init__(self, threshold: float = 0.5):
        self.threshold = threshold
        self._residual: Dict = {}

    def compress_push(self, arr, state_key=None):
        if state_key not in self._residual:
            self._residual[state_key] = np.zeros(arr.size, dtype=np.float32)
        packed = two_bit_quantize(arr.astype(np.float32),
                                  self._residual[state_key], self.threshold)
        return packed, np.asarray([self.threshold], np.float32), "2bit"

    def push_tag(self, num_elems: int = 0) -> str:
        return "2bit"


class MPQCompressor(Compressor):
    """Mixed-Precision Quantization: route by tensor size (reference:
    examples/cnn_mpq.py + MXNET_KVSTORE_SIZE_LOWER_BOUND,
    kvstore_dist_server.h:183) — small tensors go FP16, large tensors BSC."""

    type_name = "mpq"

    def __init__(self, threshold: float = 0.01, size_lower_bound: int = 200000,
                 device: bool = False):
        self.size_lower_bound = size_lower_bound
        if device:
            # the large-tensor path is exactly what the device kernels
            # exist for (>= size_lower_bound elements go BSC)
            self._bsc = _ops().DeviceBSCCompressor(threshold)
        else:
            self._bsc = BSCCompressor(threshold)
        self._fp16 = FP16Compressor()

    def _route(self, num_elems: int) -> Compressor:
        return self._bsc if num_elems >= self.size_lower_bound else self._fp16

    def compress_push(self, arr, state_key=None, **drawn):
        return self._route(arr.size).compress_push(arr, state_key, **drawn)

    def compress_pull(self, tag, arr, factor):
        if tag == "bsc":
            return self._bsc.compress_pull(tag, arr, factor)
        return self._fp16.compress_pull(tag, arr, factor)

    def pull_compr_tag(self, num_elems: int = 0) -> str:
        return self._route(num_elems).pull_compr_tag(num_elems)

    def push_tag(self, num_elems: int = 0) -> str:
        return self._route(num_elems).push_tag(num_elems)


def _host_bsc(gc, num_elems: int) -> Optional[BSCCompressor]:
    """The host Bi-Sparse pass that ``gc.compress_push`` of a key of
    ``num_elems`` elements runs: ``gc`` configured as such or reached by
    MPQ's route for this size; None for every other compressor (the
    device one included)."""
    if isinstance(gc, MPQCompressor):
        gc = gc._route(num_elems)
    return gc if isinstance(gc, BSCCompressor) else None


def takes_pairs(gc, num_elems: int) -> bool:
    """Whether ``gc.compress_push`` of a key of ``num_elems`` elements
    takes its gradient as :class:`Pairs`: the host Bi-Sparse pass. Every
    other compressor reads an array."""
    return _host_bsc(gc, num_elems) is not None


def draw_ahead(gc, num_elems: int, state_key) -> Optional[np.ndarray]:
    """:meth:`BSCCompressor.draw` where ``gc.compress_push`` of this key
    runs the host Bi-Sparse pass: the positions to hand it as
    ``positions=``. None where it does not: such a compressor keeps
    whatever it shares to itself, and its keys go one after the other."""
    bsc = _host_bsc(gc, num_elems)
    return None if bsc is None else bsc.draw(num_elems, state_key)


def make_compressor(params: Optional[dict]) -> Compressor:
    """Build from set_gradient_compression params (reference: SetParams,
    gradient_compression.cc:46-58; MPQ added per examples/cnn_mpq.py)."""
    if not params:
        return Compressor()
    ctype = params.get("type", "none")
    if ctype == "none":
        return Compressor()
    if ctype == "fp16":
        return FP16Compressor()
    if ctype == "bsc":
        threshold = float(params.get("threshold", 0.01))
        use_device = params.get("device")
        if use_device is None:
            use_device = _ops().device_compression_enabled()
        if use_device:
            return _ops().DeviceBSCCompressor(threshold)
        return BSCCompressor(threshold)
    if ctype == "2bit":
        return TwoBitCompressor(float(params.get("threshold", 0.5)))
    if ctype == "mpq":
        use_device = params.get("device")
        if use_device is None:
            use_device = _ops().device_compression_enabled()
        return MPQCompressor(
            float(params.get("threshold", 0.01)),
            int(params.get("size_lower_bound", 200000)),
            device=bool(use_device))
    raise ValueError(f"Unknown gradient compression type {ctype!r}")
