"""MultiGPS: multiple global servers (reference: scripts/cpu/run_multi_gps.sh,
DMLC_NUM_GLOBAL_SERVER=2, README.md "MultiGPS" load-balancing feature).

Keys are sharded across global servers by the deterministic heuristic
(small keys hash to one server via (key*9973)%n, big keys split across
all of them — reference EncodeDefaultKey, kvstore_dist.h:725-762); every
global server owns its canonical ranges and the round must complete with
exact values on every path."""

import numpy as np
import pytest

from tests.harness import Topology, _parallel
from geomx_tpu.kvstore import sharding
from geomx_tpu.optimizer import SGD


def test_sharding_spreads_keys_across_global_servers():
    # with 2 servers, small keys land on both (hash), big keys split
    ranks = {sharding.assign(k, 10, 2, 1000)[0].server_rank
             for k in range(8)}
    assert ranks == {0, 1}
    shards = sharding.assign(3, 5000, 2, 1000)
    assert {s.server_rank for s in shards} == {0, 1}
    assert sum(s.length for s in shards) == 5000


@pytest.mark.parametrize("spp", [1, 2])
def test_multi_gps_training_exact(spp):
    """2 global servers x (1 or 2) servers per party: small keys hash to
    one global server, the big key splits across both; after each round
    every worker sees exactly w0 - 4r."""
    topo = Topology(num_global_servers=2, servers_per_party=spp,
                    bigarray_bound=16).start(sync_global=True)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        # key 1: big -> split across both global servers; keys 2,3: small
        # -> hashed ((2*9973)%2=0, (3*9973)%2=1) one per global server
        w0 = {1: np.arange(48, dtype=np.float32),
              2: np.full(8, 5.0, np.float32),
              3: np.linspace(0, 1, 12).astype(np.float32)}

        def init_on(kv):
            for k, v in w0.items():
                kv.init(k, v)

        _parallel([lambda kv=kv: init_on(kv)
                   for kv in topo.workers + [topo.master]])

        def train(kv):
            for r in range(1, 4):
                for k in w0:
                    kv.push(k, np.ones_like(w0[k]))
                outs = {k: np.zeros_like(w0[k]) for k in w0}
                for k in w0:
                    kv.pull(k, out=outs[k])
                kv.wait()
                for k in w0:
                    np.testing.assert_allclose(
                        outs[k], w0[k] - 4.0 * r,
                        err_msg=f"key {k} round {r} (spp={spp})")

        _parallel([lambda kv=kv: train(kv) for kv in topo.workers])
    finally:
        topo.stop()


def test_multi_gps_mixed_sync():
    """MixedSync with 2 global servers: per-push updates still land on
    the right canonical shard; final state has all parties applied."""
    topo = Topology(num_global_servers=2, bigarray_bound=16).start(
        sync_global=False)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.zeros(40, np.float32)
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in topo.workers + [topo.master]])

        def train(kv):
            kv.push(0, np.ones(40, np.float32))
            out = np.zeros(40, np.float32)
            kv.pull(0, out=out)
            kv.wait()
            assert out[0] in (-2.0, -4.0), out

        _parallel([lambda kv=kv: train(kv) for kv in topo.workers])
        final = topo.master.pull(0)
        np.testing.assert_allclose(final, np.full(40, -4.0))
    finally:
        topo.stop()


def test_multi_gps_optimizer_states_cover_both_servers(tmp_path):
    """Each global server owns states for ITS canonical shards; a save
    must merge both (keyed by global rank)."""
    import json

    from geomx_tpu import checkpoint as ck
    from geomx_tpu.optimizer import Adam

    topo = Topology(num_global_servers=2, bigarray_bound=16).start(
        sync_global=True)
    fname = str(tmp_path / "mgps.states")
    try:
        topo.master.set_optimizer(Adam(learning_rate=0.01))
        w0 = np.ones(48, np.float32)   # big: split across both
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in topo.workers + [topo.master]])

        def push_pull(kv):
            kv.push(0, np.ones(48, np.float32))
            kv.pull(0)
            kv.wait()

        _parallel([lambda kv=kv: push_pull(kv) for kv in topo.workers])
        topo.workers[0].save_optimizer_states(fname)
        with open(fname) as f:
            per_server = json.load(f)
        assert set(per_server) == {"0", "1"}, per_server.keys()
        shard_offsets = set()
        for hexs in per_server.values():
            states = ck.deserialize_states(bytes.fromhex(hexs))
            for (key, off), s in states.items():
                assert key == 0 and s["t"] == 1
                shard_offsets.add(off)
        assert shard_offsets == {0, 24}, shard_offsets
    finally:
        topo.stop()


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
