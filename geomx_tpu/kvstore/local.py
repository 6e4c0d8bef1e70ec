"""Single-process KVStore ("local" / "device").

Plays the role of the reference's KVStoreLocal (reference:
src/kvstore/kvstore_local.h): an in-process store with aggregate-on-push
and an optional updater. On TPU the heavy path — multi-device gradient
aggregation — should happen inside the jitted train step via ``psum``
(see geomx_tpu.parallel); this class is the host-side store used for
single-host workflows and as the shared aggregation logic for the dist
worker's local device reduction.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from geomx_tpu.kvstore.base import KVStore, _sum_values
from geomx_tpu.kvstore.frontier import RoundFuture


class KVStoreLocal(KVStore):
    def __init__(self):
        super().__init__()
        self._store: Dict[int, np.ndarray] = {}
        self._updater = None

    @property
    def type(self) -> str:
        return "local"

    def init(self, key, value) -> None:
        keys = self._as_key_list(key)
        values = value if isinstance(value, (list, tuple)) and len(keys) > 1 else [value]
        assert len(keys) == len(values)
        for k, v in zip(keys, values):
            assert k not in self._store, f"duplicate init of key {k}"
            self._store[k] = np.array(np.asarray(v), dtype=None, copy=True)

    def push(self, key, value, priority: int = 0) -> None:
        keys = self._as_key_list(key)
        values = value if isinstance(value, (list, tuple)) and len(keys) > 1 else [value]
        for k, v in zip(keys, values):
            merged = _sum_values(v)
            if self._updater is not None:
                self._store[k] = np.asarray(self._updater(k, merged, self._store[k]))
            else:
                # no updater: aggregate into the stored value (reference
                # local-store semantics: push overwrites with the reduction)
                self._store[k] = merged

    def pull(self, key, out=None, priority: int = 0):
        keys = self._as_key_list(key)
        results = [self._store[k] for k in keys]
        if out is not None:
            outs = out if isinstance(out, (list, tuple)) else [out]
            for o, r in zip(outs, results):
                np.copyto(np.asarray(o), r)
        return results[0] if len(results) == 1 else results

    # -- row-sparse (reference: kvstore.h:59 PullRowSparse; row_sparse
    # storage type of kvstore_local.h) ----------------------------------

    def push_row_sparse(self, key, row_ids, values, priority: int = 0) -> None:
        """Push only the touched rows of a 2-D key; rows aggregate by sum
        (then the updater applies, when set)."""
        w = self._store[key]
        ids = np.asarray(row_ids, dtype=np.int64).ravel()
        rows = np.asarray(values, dtype=np.float32).reshape(ids.size, -1)
        dense = np.zeros_like(w, dtype=np.float32).reshape(
            -1, rows.shape[1])
        np.add.at(dense, ids, rows)
        self.push(key, dense.reshape(w.shape), priority)

    def pull_row_sparse(self, key, row_ids, priority: int = 0) -> np.ndarray:
        """Gather the requested rows (reference: PullRowSparse). The key
        must hold a 2-D (rows x row_len) value."""
        ids = np.asarray(row_ids, dtype=np.int64).ravel()
        w = np.asarray(self._store[key])
        return w.reshape(-1, w.shape[-1])[ids].copy()

    # -- the element-sparse round (KVStoreDist.push_pull_bsc_batch_async) --

    def push_pull_bsc_batch_async(self, keys, values_list, indices_list,
                                  priority: int = 0,
                                  slice_bytes: Optional[int] = None
                                  ) -> RoundFuture:
        """The sparse round, answered on the spot: with one worker and
        no updater the aggregate of a selection is the selection itself
        less its entries of value 0, in index order — what the servers'
        ack carries. Per key ``(values float32, flat_indices int64)``
        through a :class:`RoundFuture` that is already complete. The
        stored values are not touched (nothing reads them back in
        aggregator mode); ``priority`` and ``slice_bytes`` order and cut
        a wire this store does not have."""
        if self._updater is not None:
            raise RuntimeError(
                "the sparse round returns the aggregated selection: "
                "with an updater set the store holds weights — "
                "DeviceResidentTrainer requires aggregator mode")
        keys = list(keys)
        fut = RoundFuture(keys)
        for k, values, indices in zip(keys, values_list, indices_list):
            vals = np.asarray(values, dtype=np.float32).ravel()
            idx = np.asarray(indices, dtype=np.int64).ravel()
            if vals.size != idx.size:
                raise ValueError(f"key {k}: {vals.size} values for "
                                 f"{idx.size} indices")
            total = self._store[k].size
            if idx.size and (idx.min() < 0 or idx.max() >= total):
                raise IndexError(
                    f"push_pull_bsc_batch_async: indices out of range "
                    f"for key {k} ({total} elements)")
            keep = np.flatnonzero(vals)
            keep = keep[np.argsort(idx[keep], kind="stable")]
            fut.complete_key(k, (vals[keep], idx[keep]))
        return fut

    def set_updater(self, updater) -> None:
        self._updater = updater

    def set_optimizer(self, optimizer) -> None:
        self._updater = optimizer
        self._optimizer = optimizer  # for save/load_optimizer_states
