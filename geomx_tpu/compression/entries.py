"""A Bi-Sparse round's aggregate while it is sparse.

Every worker pushes ~1% of a key and every party server re-selects ~1%
of its aggregate, so a round's aggregate fills a few percent of the key
at most. :class:`Entries` carries it as what the ``bsc`` / ``bsc16``
wire already is — sorted, unique positions and float32 values, plus the
range's length — so a server sums index lists instead of scattering each
push into ``np.zeros(n)`` and finding the support again with
``np.nonzero``. :class:`Pairs` is the same without a promise of order:
a worker's selection as the wire hands it over (the device step's
``ops.select`` writes each key's positions ascending; a sort-based
selection gives them by magnitude), which a party server adds into its
Bi-Sparse state where it is (``Pairs.add_into``) and orders only to
merge it with a second one. Every pass here is
O(entries), none is O(n) except ``dense``, which whoever truly needs an
array calls once.

Arrays are never written after construction: a server hands the same
``idx`` / ``vals`` to every puller of a round and keeps the wire's own
arrays where it can.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence, Tuple

import numpy as np

from geomx_tpu import kernels_native

log = logging.getLogger("geomx.compression")

__all__ = ["Pairs", "Entries", "SPARSE_TAGS", "CODED", "encode_positions",
           "decode_positions", "encode_positions_numpy",
           "decode_positions_numpy", "plain_positions"]

# wire tags whose payload is (values, positions): float32 or float16 values
SPARSE_TAGS = ("bsc", "bsc16")

# A positions part of this type is no list of positions but their code:
# strictly ascending positions as the gaps between them, the first one
# absolute, each an unsigned LEB128 varint (7 bits a byte, low bits
# first, the high bit says "more"), zero bytes behind the last up to a
# multiple of 4 so that the parts behind it in a frame stay aligned.
# Positions themselves only ever travel as int32 or int64, so the part's
# own dtype (``Meta.dtypes``) tells the two apart. It crosses the
# party-global link only (``kvstore/server.py``); the tag goes on naming
# how the VALUES travel. ``decode_positions`` is the one reader of it:
# whoever else reads a positions part calls ``plain_positions``.
CODED = np.dtype(np.uint8)


def _itype(size: int):
    """The position type of :class:`Entries` over ``size`` elements."""
    return np.int32 if size <= np.iinfo(np.int32).max else np.int64


_WHAT_WAS_WRONG = {
    1: "the bytes end before the last position",
    2: "a gap of more than 64 bits",
    3: "a gap of 0: the positions do not ascend strictly",
    4: "a position outside the range",
    5: "more positions than values",
}


def _refuse(code: int, count: int, size: int):
    raise ValueError(
        f"coded positions ({count} expected, under {size}): "
        f"{_WHAT_WAS_WRONG[code]}")


def encode_positions_numpy(idx: np.ndarray) -> Optional[np.ndarray]:
    """:func:`encode_positions` in numpy passes: the reference, and what
    runs where the native library cannot be built."""
    idx = np.asarray(idx).ravel()
    n = idx.size
    if not n:
        return np.zeros(0, dtype=CODED)
    gaps = np.empty(n, dtype=np.int64)
    gaps[0] = idx[0]
    np.subtract(idx[1:], idx[:-1], out=gaps[1:], dtype=np.int64)
    if gaps[0] < 0 or (n > 1 and gaps[1:].min() < 1):
        return None
    gaps = gaps.view(np.uint64)
    nbytes = np.ones(n, dtype=np.int64)     # a byte every 7 bits
    for k in range(1, 10):
        longer = gaps >= np.uint64(1 << 7 * k)
        if not longer.any():
            break
        nbytes += longer
    ends = np.cumsum(nbytes)
    starts = ends - nbytes
    out = np.zeros(-(-int(ends[-1]) // 4) * 4, dtype=CODED)
    for k in range(int(nbytes.max())):
        has = np.flatnonzero(nbytes > k)
        byte = ((gaps[has] >> np.uint64(7 * k))
                & np.uint64(0x7f)).astype(CODED)
        byte[nbytes[has] > k + 1] |= 0x80
        out[starts[has] + k] = byte
    return out


def decode_positions_numpy(buf: np.ndarray, count: int,
                           size: int) -> np.ndarray:
    """:func:`decode_positions` in numpy passes: the reference, and what
    runs where the native library cannot be built."""
    buf = np.asarray(buf, dtype=CODED).ravel()
    if not count:
        if buf.size:
            _refuse(5, count, size)
        return np.zeros(0, dtype=_itype(size))
    last = np.flatnonzero(buf < 0x80)       # the bytes that end a gap
    if last.size < count:
        _refuse(1, count, size)
    ends = last[:count] + 1
    tail = buf[ends[-1]:]
    if tail.size > 3 or tail.any():
        _refuse(5, count, size)
    starts = np.concatenate(([0], ends[:-1]))
    nbytes = ends - starts
    if nbytes.max() > 10:
        _refuse(2, count, size)
    gaps = np.zeros(count, dtype=np.uint64)
    for k in range(int(nbytes.max())):
        has = np.flatnonzero(nbytes > k)
        bits = buf[starts[has] + k].astype(np.uint64) & np.uint64(0x7f)
        if k == 9 and (bits > 1).any():
            _refuse(2, count, size)
        gaps[has] |= bits << np.uint64(7 * k)
    if not gaps[1:].all():
        _refuse(3, count, size)
    if size <= 0 or (gaps >= np.uint64(size)).any():
        _refuse(4, count, size)
    # every gap is under size < 2**63: a sum cannot wrap before it is
    # over size, so the largest one under size clears them all
    idx = np.cumsum(gaps, dtype=np.uint64)
    if idx.max() >= np.uint64(size):
        _refuse(4, count, size)
    return idx.astype(_itype(size))


def encode_positions(idx: np.ndarray) -> Optional[np.ndarray]:
    """The positions ``idx`` (int32 or int64) as the code :data:`CODED`
    describes, padding included; None, for the caller to send them as
    they are, where they do not ascend strictly from 0 or more. One
    scalar pass (``native/kernels.cc``, the GIL released), which checks
    the order as it goes."""
    idx = np.ascontiguousarray(idx).ravel()
    if (kernels_native.lib() is None or idx.dtype.kind != "i"
            or idx.dtype.itemsize < 4):
        return encode_positions_numpy(idx)
    return kernels_native.idx_encode(idx)


def decode_positions(buf: np.ndarray, count: int, size: int) -> np.ndarray:
    """``count`` positions of a range of ``size`` elements out of the
    code ``buf`` (see :data:`CODED`): strictly ascending, each in
    ``[0, size)``, ``int32`` where ``size`` allows, which is what the
    decoder holds the bytes to as it reads them. Padding is not read.
    ``ValueError`` for bytes that end early, a gap over 64 bits or of
    0, a position outside the range, or more behind the last position
    than padding: never a shorter list."""
    buf = np.ascontiguousarray(buf, dtype=CODED).ravel()
    if kernels_native.lib() is None:
        return decode_positions_numpy(buf, count, size)
    out = np.empty(count, dtype=_itype(size))
    code = kernels_native.idx_decode(buf, out, size)
    if code:
        _refuse(code, count, size)
    return out


def plain_positions(aux) -> np.ndarray:
    """A positions part for a reader that takes positions as they are:
    flat, and refused where it is the code (its bytes would read as
    positions 0..255)."""
    idx = np.asarray(aux).ravel()
    if idx.dtype == CODED:
        raise ValueError(
            "a coded positions part reached a reader of plain positions")
    return idx


def _sum_runs(idx: np.ndarray, vals: np.ndarray):
    """Sum the values of equal neighbours of a sorted ``idx`` (float32,
    left to right: the order a stable sort kept is the order of arrival,
    so two terms add exactly as the dense ``+=`` adds them)."""
    if idx.size < 2:
        return idx, vals
    first = np.empty(idx.size, dtype=bool)
    first[0] = True
    np.not_equal(idx[1:], idx[:-1], out=first[1:])
    starts = np.flatnonzero(first)
    if starts.size == idx.size:
        return idx, vals
    return idx[starts], np.add.reduceat(vals, starts)


class Pairs:
    """``size`` float32 elements given as (position, value) pairs: every
    ``idx`` lies in ``[0, size)``, in any order; a position that repeats
    holds the sum of its values; every other element is 0."""

    __slots__ = ("idx", "vals", "size")
    dtype = np.dtype(np.float32)

    def __init__(self, idx: np.ndarray, vals: np.ndarray, size: int):
        self.idx, self.vals, self.size = idx, vals, int(size)

    @classmethod
    def from_wire(cls, val, aux, size: int) -> "Pairs":
        """A ``bsc`` / ``bsc16`` payload addressing ``size`` elements,
        as the wire's own arrays (float16 values widen). Positions
        outside the range are dropped with a warning. O(entries), no
        sort. A coded positions part (:data:`CODED`) is decoded into
        :class:`Entries`: the decoder has held it to the order and the
        range, so neither is looked at again."""
        if aux is None:
            raise ValueError("bsc payload missing index aux array")
        idx = np.asarray(aux).ravel()
        vals = np.asarray(val, dtype=np.float32).ravel()
        if idx.dtype == CODED:
            return Entries(decode_positions(idx, vals.size, size), vals,
                           size)
        if idx.dtype.kind not in "iu":
            idx = idx.astype(np.int64)
        if idx.size and not (idx.min() >= 0 and idx.max() < size):
            ok = (idx >= 0) & (idx < size)
            log.warning(
                "bsc push: dropping %d out-of-range indices "
                "(payload addresses %d elements)",
                int((~ok).sum()), size)
            idx, vals = idx[ok], vals[ok]
        return cls(idx, vals, size)

    def entries(self) -> "Entries":
        """These pairs in order, each position once (equal positions
        summed in the order they came)."""
        order = np.argsort(self.idx, kind="stable")
        idx, vals = _sum_runs(self.idx[order], self.vals[order])
        return Entries(idx.astype(_itype(self.size), copy=False), vals,
                       self.size)

    def __getitem__(self, s: slice):
        """The range ``[s.start, s.stop)`` with positions relative to its
        start, as slicing the dense array gives."""
        lo, hi, step = s.indices(self.size)
        if step != 1:
            raise IndexError("contiguous ranges only")
        if lo == 0 and hi == self.size:
            return self
        idx, vals = self._between(lo, hi)
        return type(self)(idx - lo if lo else idx, vals, max(hi - lo, 0))

    def _between(self, lo: int, hi: int):
        """(idx, vals) of the pairs at positions ``[lo, hi)``: a mask,
        O(entries) a cut and no sort."""
        keep = (self.idx >= lo) & (self.idx < hi)
        return self.idx[keep], self.vals[keep]

    def add_into(self, out: np.ndarray) -> None:
        """``out += dense()`` at the positions held, without the array:
        what a float32 ``out`` gets at a position held once is bit for
        bit what the dense ``+=`` gives it."""
        np.add.at(out, self.idx, self.vals)

    def dense(self) -> np.ndarray:
        """A new float32 array of ``size`` elements. The one O(n) pass."""
        out = np.zeros(self.size, dtype=np.float32)
        self.add_into(out)
        return out


class Entries(Pairs):
    """:class:`Pairs` in order: ``idx`` sorted and unique."""

    __slots__ = ()

    @classmethod
    def from_wire(cls, val, aux, size: int) -> "Entries":
        """A ``bsc`` / ``bsc16`` payload addressing ``size`` elements.

        Positions outside the range are dropped with the warning the
        dense scatter gives; duplicate positions inside one payload sum
        (the wire's contract). A payload already in order — what
        every server's selection and response is — is taken as it
        is, its float32 values without a copy."""
        pairs = Pairs.from_wire(val, aux, size)
        if isinstance(pairs, Entries):
            return pairs
        idx = pairs.idx
        if idx.size < 2 or bool((idx[1:] > idx[:-1]).all()):
            return cls(idx.astype(_itype(size), copy=False), pairs.vals,
                       size)
        return pairs.entries()

    def entries(self) -> "Entries":
        return self

    def _between(self, lo: int, hi: int):
        a, b = np.searchsorted(self.idx, (lo, hi))
        return self.idx[a:b], self.vals[a:b]

    def placed(self, offset: int, size: int) -> "Entries":
        """These entries as part of a range of ``size`` elements that
        they start at ``offset`` of."""
        if not offset and size == self.size:
            return self
        return Entries(self.idx + offset if offset else self.idx,
                       self.vals, size)

    def add(self, other: "Entries") -> "Entries":
        """Element-wise sum with ``other`` (same ``size``): a merge of
        the two index lists, equal positions summed in float32."""
        return self.merge(other)[0]

    def merge(self, other: "Entries") -> Tuple["Entries", Optional[bool]]:
        """:meth:`add`, and which pass made the sum: True the native one
        (``native/kernels.cc`` ``gxk_entries_merge``: one linear pass over
        two lists that both ascend, the GIL released), False the numpy
        chain beside it (the reference, and what runs where the library
        is not loaded, the operands are not contiguous arrays of one
        position type, or one of them turns out not to ascend), None
        where there was nothing to pass over. The entries are the same
        bit for bit: ``self`` is the earlier arriver, its term the first
        of a sum."""
        if not other.idx.size:
            return self, None
        if not self.idx.size:
            return other, None
        if self.idx[-1] < other.idx[0]:
            # slices of one key arriving in order: already merged
            return Entries(np.concatenate((self.idx, other.idx)),
                           np.concatenate((self.vals, other.vals)),
                           self.size), None
        if kernels_native.entries_merge_usable(self.idx, self.vals,
                                               other.idx, other.vals):
            merged = kernels_native.entries_merge(self.idx, self.vals,
                                                  other.idx, other.vals)
            if merged is not None:
                return Entries(*merged, self.size), True
            log.warning(
                "entries of %d and %d positions do not both ascend: "
                "merged by sorting them", self.idx.size, other.idx.size)
        idx = np.concatenate((self.idx, other.idx))
        vals = np.concatenate((self.vals, other.vals))
        order = np.argsort(idx, kind="stable")
        return Entries(*_sum_runs(idx[order], vals[order]), self.size), False

    def nonzero(self) -> "Entries":
        """Without the entries whose value is exactly 0 (an explicit
        zero a party pushed, a sum that cancelled): the set
        ``np.nonzero`` of the dense array finds."""
        keep = self.vals != 0
        if keep.all():
            return self
        return Entries(self.idx[keep], self.vals[keep], self.size)

    @staticmethod
    def concat(parts: Sequence["Entries"]) -> "Entries":
        """Adjacent ranges joined in order (``np.concatenate`` of the
        dense arrays)."""
        if len(parts) == 1:
            return parts[0]
        size = sum(p.size for p in parts)
        idx, start = [], 0
        for p in parts:
            idx.append(p.idx + start if start else p.idx)
            start += p.size
        return Entries(np.concatenate(idx),
                       np.concatenate([p.vals for p in parts]), size)

    @property
    def sparse(self) -> bool:
        """An entry costs 8 bytes, an element 4: past half full the
        array is the smaller store (a ``bsc`` pull of dense weights)."""
        return 2 * self.idx.size <= self.size

    def dense(self) -> np.ndarray:
        """As :meth:`Pairs.dense`, by assignment: no position repeats."""
        out = np.zeros(self.size, dtype=np.float32)
        out[self.idx] = self.vals
        return out
