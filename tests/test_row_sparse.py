"""Row-sparse push/pull (reference: include/mxnet/kvstore.h:59
PullRowSparse; src/kvstore/kvstore_dist.h:906 EncodeRowSparseKey).

Embedding-style updates: push only the touched rows, pull only the
requested rows; overlapping rows from different workers aggregate by
sum before the optimizer applies."""

import numpy as np
import pytest

from geomx_tpu.kvstore.local import KVStoreLocal
from geomx_tpu.optimizer import SGD
from tests.harness import Topology, _parallel


def test_local_row_sparse_roundtrip():
    kv = KVStoreLocal()
    kv.set_optimizer(SGD(learning_rate=1.0))
    w0 = np.arange(20, dtype=np.float32).reshape(5, 4)
    kv.init(0, w0)
    kv.push_row_sparse(0, [1, 3, 1], np.ones((3, 4), np.float32))
    rows = kv.pull_row_sparse(0, [0, 1, 3])
    np.testing.assert_allclose(rows[0], w0[0])          # untouched
    np.testing.assert_allclose(rows[1], w0[1] - 2.0)    # pushed twice
    np.testing.assert_allclose(rows[2], w0[3] - 1.0)


def test_dist_row_sparse_hips_topology():
    """Full two-tier path: rsp pushes scatter to dense at the party
    server, aggregate through the global tier, and rsp pulls gather the
    fresh rows."""
    topo = Topology().start(sync_global=True)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.arange(48, dtype=np.float32).reshape(12, 4)
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in topo.workers + [topo.master]])

        def train(kv):
            # every worker touches rows {2, 7}; worker-distinct row =
            # 2 + rank to also cover non-overlapping rows
            ids = np.array([2, 7], np.int64)
            kv.push_row_sparse(0, ids, np.ones((2, 4), np.float32))
            rows = kv.pull_row_sparse(0, [2, 7, 0])
            kv.wait()
            np.testing.assert_allclose(rows[0], w0[2] - 4.0)  # 4 workers
            np.testing.assert_allclose(rows[1], w0[7] - 4.0)
            np.testing.assert_allclose(rows[2], w0[0])        # untouched

        _parallel([lambda kv=kv: train(kv) for kv in topo.workers])

        # dense pull sees the same state
        def check(kv):
            out = np.zeros((12, 4), np.float32)
            kv.pull(0, out=out)
            kv.wait()
            expect = w0.copy()
            expect[2] -= 4.0
            expect[7] -= 4.0
            np.testing.assert_allclose(out, expect)

        _parallel([lambda kv=kv: check(kv) for kv in topo.workers])
    finally:
        topo.stop()


def test_dist_row_sparse_rejects_sharded_key():
    topo = Topology(servers_per_party=2, bigarray_bound=16).start(
        sync_global=True)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.zeros((12, 4), np.float32)   # 48 elems > bound: sharded
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in topo.workers + [topo.master]])
        with pytest.raises(AssertionError, match="sharded"):
            topo.workers[0].push_row_sparse(
                0, [1], np.ones((1, 4), np.float32))
    finally:
        topo.stop()


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
