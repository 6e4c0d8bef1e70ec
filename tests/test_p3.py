"""P3 priority-propagation tests (reference: P3_EncodeDefaultKey,
kvstore_dist.h:768-805 + the priority send thread, van.cc:548,851)."""

import numpy as np
import pytest

from geomx_tpu.kvstore import sharding
from geomx_tpu.optimizer import SGD
from tests.harness import SingleTier, _parallel


def test_assign_p3_covers_and_respects_canonical_ranges():
    shards = sharding.assign_p3(3, 100, 4, 16)
    assert sum(s.length for s in shards) == 100
    offs = [s.offset for s in shards]
    assert offs == sorted(offs)
    assert all(s.length <= 16 for s in shards)
    # contiguous coverage
    pos = 0
    for s in shards:
        assert s.offset == pos
        pos += s.length
    # every slice lies INSIDE its server's canonical assign() range — the
    # global-store server validates offsets against these (server.py
    # _canonical_ranges), so P3 slicing must not re-route across servers
    canon = {c.server_rank: c for c in sharding.assign(3, 100, 4, 16)}
    for s in shards:
        c = canon[s.server_rank]
        assert c.offset <= s.offset
        assert s.offset + s.length <= c.offset + c.length
    # zero-size keys still get one shard
    z = sharding.assign_p3(1, 0, 4, 16)
    assert len(z) == 1 and z[0].length == 0


def test_assign_p3_small_key_single_slice():
    shards = sharding.assign_p3(7, 10, 4, 16)
    assert len(shards) == 1
    assert shards[0].server_rank == (7 * 9973) % 4
    assert shards[0].length == 10


def test_p3_single_tier_push_pull():
    """Single-tier PS with ENABLE_P3: keys sliced at bigarray granularity,
    per-slice messages through the priority queue; results must be exact."""
    with SingleTier(extra={"enable_p3": True, "bigarray_bound": 16}) as topo:
        kvs = topo.workers
        rank0 = next(kv for kv in kvs if kv.rank == 0)
        rank0.set_optimizer(SGD(learning_rate=0.5))
        # key 0 is big (sliced into 3 slices of <=16), key 1 small
        w = {0: np.arange(40, dtype=np.float32), 1: np.ones(8, np.float32)}
        _parallel([lambda kv=kv: [kv.init(k, v) for k, v in w.items()]
                   for kv in kvs])

        def train(kv):
            # later keys get higher priority (reference: push(idx, g,
            # priority=-idx) in examples/cnn.py:123)
            for k in w:
                kv.push(k, np.ones_like(w[k]), priority=-k)
            outs = {k: np.zeros_like(w[k]) for k in w}
            for k in w:
                kv.pull(k, out=outs[k], priority=-k)
            kv.wait()
            for k in w:
                np.testing.assert_allclose(outs[k], w[k] - 1.0)  # 0.5*2 workers

        _parallel([lambda kv=kv: train(kv) for kv in kvs])


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
