"""Integration tests: the full HiPS two-tier topology, in one process.

Replicates the reference's 12-process, 3-party demo topology
(scripts/cpu/run_vanilla_hips.sh) as in-process threads: a central party
(global scheduler, global server, master worker, scheduler) plus two data
parties (scheduler, server, two workers each). Because Postoffices are
instance-scoped, no subprocesses or env vars are needed — configs are
passed explicitly.
"""

import threading

import numpy as np
import pytest

from geomx_tpu.optimizer import SGD, Adam
from tests.harness import SingleTier, Topology, _parallel


def test_hips_fsa_vanilla():
    """Vanilla dist_sync: SGD(lr=1) on the global server; 2 parties x 2
    workers each push ones -> after one round every worker pulls w0 - 4."""
    topo = Topology().start(sync_global=True)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.arange(40, dtype=np.float32).reshape(5, 8)

        def init_on(kv):
            kv.init(0, w0)
            if not kv.is_master_worker:
                got = kv.pull(0)
                np.testing.assert_allclose(got.reshape(5, 8), w0)

        _parallel([lambda kv=kv: init_on(kv)
                   for kv in topo.workers + [topo.master]])

        def train_step(kv):
            kv.push(0, np.ones((5, 8), np.float32))
            out = np.zeros((5, 8), np.float32)
            kv.pull(0, out=out)
            kv.wait()
            np.testing.assert_allclose(out, w0 - 4.0)

        _parallel([lambda kv=kv: train_step(kv) for kv in topo.workers])

        # second round: w0 - 8 everywhere
        def step2(kv):
            kv.push(0, np.ones((5, 8), np.float32))
            out = np.zeros((5, 8), np.float32)
            kv.pull(0, out=out)
            kv.wait()
            np.testing.assert_allclose(out, w0 - 8.0)

        _parallel([lambda kv=kv: step2(kv) for kv in topo.workers])
    finally:
        topo.stop()


def test_hips_multiple_keys_and_adam():
    topo = Topology().start(sync_global=True)
    try:
        topo.master.set_optimizer(Adam(learning_rate=0.01))
        shapes = {0: (4, 4), 1: (16,), 2: (3, 2, 2)}
        w0 = {k: np.random.RandomState(k).randn(*s).astype(np.float32)
              for k, s in shapes.items()}

        def init_on(kv):
            for k in shapes:
                kv.init(k, w0[k])

        _parallel([lambda kv=kv: init_on(kv)
                   for kv in topo.workers + [topo.master]])

        outs = {}
        lock = threading.Lock()

        def train(kv):
            grads = {k: np.full(shapes[k], 0.1, np.float32) for k in shapes}
            for k in shapes:
                kv.push(k, grads[k], priority=-k)
            res = {k: np.zeros(shapes[k], np.float32) for k in shapes}
            for k in shapes:
                kv.pull(k, out=res[k], priority=-k)
            kv.wait()
            with lock:
                outs[kv.rank, id(kv)] = res

        _parallel([lambda kv=kv: train(kv) for kv in topo.workers])
        vals = list(outs.values())
        for other in vals[1:]:
            for k in shapes:
                np.testing.assert_allclose(vals[0][k], other[k], rtol=1e-6)
        for k in shapes:  # Adam moved every weight
            assert not np.allclose(vals[0][k], w0[k])
    finally:
        topo.stop()


def test_hips_mixed_sync_async_global():
    """dist_async (MixedSync): global tier updates per party push."""
    topo = Topology().start(sync_global=False)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.zeros(8, np.float32)
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in topo.workers + [topo.master]])

        def train(kv):
            kv.push(0, np.ones(8, np.float32))
            out = np.zeros(8, np.float32)
            kv.pull(0, out=out)
            kv.wait()
            # each party contributes -2; depending on arrival order a worker
            # sees one or both parties applied
            assert out[0] in (-2.0, -4.0), out

        _parallel([lambda kv=kv: train(kv) for kv in topo.workers])
        # both parties' push acks returned, so the global store has both
        # updates; the master worker's local server IS the global server,
        # so its pull reads the global store directly
        final = topo.master.pull(0)
        np.testing.assert_allclose(final, np.full(8, -4.0))
    finally:
        topo.stop()


def test_hips_bsc_gradient_aggregation():
    """BSC mode: no global optimizer; the store carries the aggregated
    gradient; workers pull it (into param.grad() in the examples) and apply
    the optimizer locally (reference: examples/cnn_bsc.py:115-121)."""
    topo = Topology().start(sync_global=True)
    try:
        topo.master.set_gradient_compression({"type": "bsc", "threshold": 1.0})
        w0 = np.full(64, 7.0, np.float32)
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in topo.workers + [topo.master]])

        def train(kv):
            kv.push(0, np.full(64, 0.25, np.float32))
            out = np.zeros(64, np.float32)
            kv.pull(0, out=out)
            kv.wait()
            # 4 workers x 0.25, summed through both tiers
            np.testing.assert_allclose(out, np.full(64, 1.0), rtol=1e-5)

        _parallel([lambda kv=kv: train(kv) for kv in topo.workers])
    finally:
        topo.stop()


def test_hips_multi_server_parties():
    """Two local servers per party: big keys split across them, each server
    forwards its shard; the global server's party-weighted element counting
    must complete the round (the reference's aligned-key counting cannot)."""
    topo = Topology(servers_per_party=2, bigarray_bound=16).start(
        sync_global=True)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        # key 0: big (split across servers); key 1: small (hash-assigned)
        w = {0: np.arange(40, dtype=np.float32), 1: np.ones(8, np.float32)}

        def init_on(kv):
            for k, v in w.items():
                kv.init(k, v)

        _parallel([lambda kv=kv: init_on(kv)
                   for kv in topo.workers + [topo.master]])

        def train(kv):
            for k in w:
                kv.push(k, np.ones_like(w[k]))
            outs = {k: np.zeros_like(w[k]) for k in w}
            for k in w:
                kv.pull(k, out=outs[k])
            kv.wait()
            for k in w:
                np.testing.assert_allclose(outs[k], w[k] - 4.0)

        _parallel([lambda kv=kv: train(kv) for kv in topo.workers])
    finally:
        topo.stop()


def test_single_tier_classic_ps():
    """No global tier: a classic 1-scheduler/1-server/2-worker PS where the
    local server applies the optimizer (stock-MXNet dist behavior)."""
    with SingleTier() as topo:
        kvs = topo.workers
        rank0 = next(kv for kv in kvs if kv.rank == 0)
        rank0.set_optimizer(SGD(learning_rate=0.5))
        w0 = np.ones(10, np.float32)
        _parallel([lambda kv=kv: kv.init(3, w0) for kv in kvs])

        def train(kv):
            kv.push(3, np.ones(10, np.float32))
            out = np.zeros(10, np.float32)
            kv.pull(3, out=out)
            kv.wait()
            np.testing.assert_allclose(out, np.zeros(10))  # 1 - 0.5*2

        _parallel([lambda kv=kv: train(kv) for kv in kvs])


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
