"""A Bi-Sparse round's aggregate while it is sparse.

Every worker pushes ~1% of a key and every party server re-selects ~1%
of its aggregate, so a round's aggregate fills a few percent of the key
at most. :class:`Entries` carries it as what the ``bsc`` / ``bsc16``
wire already is — sorted, unique positions and float32 values, plus the
range's length — so a server sums index lists instead of scattering each
push into ``np.zeros(n)`` and finding the support again with
``np.nonzero``. Every pass here is O(entries), none is O(n) except
:meth:`Entries.dense`, which whoever truly needs an array calls once.

Arrays are never written after construction: a server hands the same
``idx`` / ``vals`` to every puller of a round and keeps the wire's own
arrays where they already are in order.
"""

from __future__ import annotations

import logging
from typing import Sequence

import numpy as np

__all__ = ["Entries", "SPARSE_TAGS"]

# wire tags whose payload is (values, positions): float32 or float16 values
SPARSE_TAGS = ("bsc", "bsc16")


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


class Entries:
    """``size`` float32 elements of which ``idx`` (sorted, unique, in
    ``[0, size)``) hold ``vals``; every other element is 0."""

    __slots__ = ("idx", "vals", "size")
    dtype = np.dtype(np.float32)

    def __init__(self, idx: np.ndarray, vals: np.ndarray, size: int):
        self.idx, self.vals, self.size = idx, vals, int(size)

    @classmethod
    def from_wire(cls, val, aux, size: int) -> "Entries":
        """A ``bsc`` / ``bsc16`` payload addressing ``size`` elements.

        Positions outside the range are dropped with the warning the
        dense scatter gives; duplicate positions inside one payload sum
        (the wire's contract). A payload already in order — what
        every selection and every server response is — is taken as it
        is, its float32 values without a copy."""
        if aux is None:
            raise ValueError("bsc payload missing index aux array")
        idx = np.asarray(aux).ravel()
        if idx.dtype.kind not in "iu":
            idx = idx.astype(np.int64)
        vals = np.asarray(val, dtype=np.float32).ravel()
        if idx.size and not (
                idx[0] >= 0 and idx[-1] < size
                and (idx.size < 2 or bool((idx[1:] > idx[:-1]).all()))):
            ok = (idx >= 0) & (idx < size)
            if not ok.all():
                logging.getLogger("geomx.compression").warning(
                    "bsc push: dropping %d out-of-range indices "
                    "(payload addresses %d elements)",
                    int((~ok).sum()), size)
                idx, vals = idx[ok], vals[ok]
            order = np.argsort(idx, kind="stable")
            idx, vals = _sum_runs(idx[order], vals[order])
        itype = np.int32 if size <= np.iinfo(np.int32).max else np.int64
        return cls(idx.astype(itype, copy=False), vals, size)

    def __getitem__(self, s: slice) -> "Entries":
        """The range ``[s.start, s.stop)`` with positions relative to its
        start, as slicing the dense array gives."""
        lo, hi, step = s.indices(self.size)
        if step != 1:
            raise IndexError("Entries take contiguous ranges only")
        if lo == 0 and hi == self.size:
            return self
        a, b = np.searchsorted(self.idx, (lo, hi))
        idx = self.idx[a:b]
        return Entries(idx - lo if lo else idx, self.vals[a:b],
                       max(hi - lo, 0))

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
        if not other.idx.size:
            return self
        if not self.idx.size:
            return other
        idx = np.concatenate((self.idx, other.idx))
        vals = np.concatenate((self.vals, other.vals))
        if self.idx[-1] < other.idx[0]:
            # slices of one key arriving in order: already merged
            return Entries(idx, vals, self.size)
        order = np.argsort(idx, kind="stable")
        return Entries(*_sum_runs(idx[order], vals[order]), self.size)

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
        """A new float32 array of ``size`` elements. The one O(n) pass."""
        out = np.zeros(self.size, dtype=np.float32)
        out[self.idx] = self.vals
        return out
