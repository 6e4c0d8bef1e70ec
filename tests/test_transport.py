"""Transport-layer tests: rendezvous, barriers, push/pull round-trips.

Because each tier is an independent Postoffice instance (no process-global
singletons, unlike the reference's ps::Postoffice), an entire scheduler +
server + worker topology can run inside one test process on ephemeral ports.
"""

import threading

import numpy as np
import pytest

from geomx_tpu.ps import base
from geomx_tpu.ps.kv_app import KVPairs, KVServer, KVWorker
from geomx_tpu.ps.message import Message, Meta, Node, Role
from tests.harness import make_tier, shutdown


def test_message_roundtrip():
    m = Message(
        Meta(
            sender=9,
            recver=8,
            app_id=0,
            timestamp=42,
            request=True,
            push=True,
            priority=-3,
            is_global=True,
            nodes=[Node(role=Role.WORKER, id=9, hostname="127.0.0.1", port=1234)],
        )
    )
    m.add_array(np.arange(6, dtype=np.float32).reshape(2, 3))
    m.add_array(np.array([1, 2, 3], dtype=np.int64))
    buf = m.pack()
    m2 = Message.unpack(buf)
    assert m2.meta.sender == 9 and m2.meta.recver == 8
    assert m2.meta.timestamp == 42 and m2.meta.push and m2.meta.request
    assert m2.meta.priority == -3 and m2.meta.is_global
    assert m2.meta.nodes[0].port == 1234
    np.testing.assert_array_equal(m2.get_array(0), m.get_array(0))
    np.testing.assert_array_equal(m2.get_array(1), np.array([1, 2, 3]))


def test_rendezvous_assigns_ids():
    sched, servers, workers = make_tier(num_workers=2, num_servers=2)
    try:
        assert sched.my_id == base.SCHEDULER
        assert sorted(s.my_id for s in servers) == [8, 10]
        assert sorted(w.my_id for w in workers) == [9, 11]
        # every node has the full table
        for po in servers + workers:
            assert set(po.van.node_table) == {1, 8, 9, 10, 11}
    finally:
        shutdown(sched, *servers, *workers)


def test_barrier_releases_all_members():
    sched, servers, workers = make_tier(num_workers=2, num_servers=1)
    try:
        done = []

        def do_barrier(po):
            po.barrier(base.WORKER_SERVER_GROUP, timeout=20)
            done.append(po.my_id)

        ts = [
            threading.Thread(target=do_barrier, args=(po,), daemon=True)
            for po in servers + workers
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(20)
        assert sorted(done) == sorted([8, 9, 11])
    finally:
        shutdown(sched, *servers, *workers)


def test_push_pull_roundtrip():
    sched, servers, workers = make_tier(num_workers=2, num_servers=1)
    store = {}
    try:
        server = KVServer(servers[0])

        def handle(req, kvs, srv):
            if req.push:
                for k, v in zip(kvs.keys, kvs.vals):
                    store[k] = store.get(k, 0) + v
                srv.response(req)
            elif req.pull:
                out = KVPairs(
                    keys=kvs.keys, vals=[store[k] for k in kvs.keys]
                )
                srv.response(req, out)

        server.set_request_handle(handle)

        w0 = KVWorker(workers[0])
        w1 = KVWorker(workers[1])
        v = np.ones((4, 3), dtype=np.float32)
        ts0 = w0.push(KVPairs(keys=[7], vals=[v]), server_rank=0)
        ts1 = w1.push(KVPairs(keys=[7], vals=[2 * v]), server_rank=0)
        w0.wait(ts0, 10)
        w1.wait(ts1, 10)

        ts = w0.pull([7], server_rank=0)
        w0.wait(ts, 10)
        (resp,) = w0.take_response(ts)
        np.testing.assert_allclose(resp.vals[0], 3 * v)
    finally:
        shutdown(sched, *servers, *workers)


def test_simple_app_command():
    sched, servers, workers = make_tier(num_workers=1, num_servers=1)
    got = {}
    try:
        server = KVServer(servers[0])

        def handle(req, kvs, srv):
            if req.simple_app:
                got["head"] = req.head
                got["body"] = req.body
                srv.response(req)

        server.set_request_handle(handle)
        w = KVWorker(workers[0])
        ts = w.request(head=5, body="sync_mode", recver=base.server_rank_to_id(0))
        w.wait(ts, 10)
        assert got == {"head": 5, "body": "sync_mode"}
    finally:
        shutdown(sched, *servers, *workers)


def test_two_tiers_coexist():
    """A process can be a local-tier server and a global-tier worker at once."""
    sched_l, servers_l, workers_l = make_tier(num_workers=1, num_servers=1)
    sched_g, servers_g, workers_g = make_tier(
        num_workers=1, num_servers=1, is_global=True
    )
    try:
        # the "intra-DC server" owns both: its local KVServer and a global KVWorker
        local_server = KVServer(servers_l[0])
        global_store = {}
        gserver = KVServer(servers_g[0])

        def ghandle(req, kvs, srv):
            if req.push:
                for k, v in zip(kvs.keys, kvs.vals):
                    global_store[k] = v
                srv.response(req)

        gserver.set_request_handle(ghandle)
        gworker = KVWorker(workers_g[0])

        def lhandle(req, kvs, srv):
            if req.push:
                # forward aggregated grad up to the global tier
                ts = gworker.push(kvs, server_rank=0)
                gworker.wait(ts, 10)
                srv.response(req)

        local_server.set_request_handle(lhandle)

        w = KVWorker(workers_l[0])
        v = np.full((2, 2), 5.0, dtype=np.float32)
        ts = w.push(KVPairs(keys=[3], vals=[v]), server_rank=0)
        w.wait(ts, 10)
        np.testing.assert_allclose(global_store[3], v)
    finally:
        shutdown(sched_l, *servers_l, *workers_l, sched_g, *servers_g, *workers_g)


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))


def test_binary_meta_roundtrip_all_field_kinds():
    """FLAG_BINMETA TLV codec: every field kind survives pack/unpack
    bit-exactly, and node-table messages stay JSON (round-4 verdict
    item 5: JSON meta was the hot path's largest per-message CPU)."""
    from geomx_tpu.ps.message import (FLAG_BINMETA, _PREHDR, Message,
                                      Meta, Node)

    m = Meta(sender=5, recver=9, app_id=0, customer_id=1, timestamp=42,
             request=True, push=True, pull=True, head=3, body="cmd",
             dtypes=["<f4", "<i8"], shapes=[[2, 3], [7]], priority=-2,
             version=11, key=123, iters=6, compr="bsc", first_key=1,
             seq=2, seq_begin=0, seq_end=4, msg_type=1, val_bytes=99,
             total_bytes=400, channel=1, tos=32, val_dtype="<f2",
             dgt_scale=0.125, dgt_n=77, lossy=True, num_merge=3,
             party_nsrv=2, aux_mask=0b101, aux_len=3, is_global=True)
    msg = Message(meta=m)
    msg.add_array(np.arange(6, dtype=np.float32).reshape(2, 3))
    wire = msg.pack()
    flags = _PREHDR.unpack_from(wire, 0)[2]
    assert flags & FLAG_BINMETA, "data-plane meta must ride the binary codec"
    back = Message.unpack(wire)
    for f in ("sender", "recver", "timestamp", "request", "push", "pull",
              "head", "body", "priority", "version", "key", "iters",
              "compr", "seq_end", "val_dtype", "dgt_scale", "dgt_n",
              "lossy", "num_merge", "party_nsrv", "aux_mask", "aux_len",
              "is_global"):
        assert getattr(back.meta, f) == getattr(m, f), f
    # add_array appended a third entry to dtypes/shapes
    assert back.meta.dtypes == ["<f4", "<i8", "<f4"]
    assert back.meta.shapes == [[2, 3], [7], [2, 3]]
    np.testing.assert_array_equal(back.get_array(0),
                                  np.arange(6, dtype=np.float32).reshape(2, 3))

    # control message with a node table falls back to JSON
    ctrl = Message(meta=Meta(control_cmd=2, nodes=[Node(id=8, port=99,
                                                        hostname="h")]))
    wire2 = ctrl.pack()
    assert not _PREHDR.unpack_from(wire2, 0)[2] & FLAG_BINMETA
    back2 = Message.unpack(wire2)
    assert back2.meta.nodes[0].port == 99


def test_binary_meta_large_fields():
    """Regressions from review: aux_mask with >=64 keys (bigint), body
    >64 KiB (optimizer-state relays), and malformed binary meta raising
    ValueError (the reader loop's drop-connection contract)."""
    import pytest

    from geomx_tpu.ps.message import (FLAG_BINMETA, _PREHDR, Message,
                                      Meta, _decode_meta)

    mask = int("1" * 200, 2)                  # 200-key batched aux mask
    big_body = "ab" * 40000                   # 80 KB command payload
    m = Meta(sender=1, recver=2, timestamp=3, aux_mask=mask,
             aux_len=200, body=big_body, simple_app=True)
    back = Message.unpack(Message(meta=m).pack())
    assert back.meta.aux_mask == mask
    assert back.meta.aux_len == 200
    assert back.meta.body == big_body

    wire = bytearray(Message(meta=m).pack())
    flags = _PREHDR.unpack_from(wire, 0)[2]
    assert flags & FLAG_BINMETA
    with pytest.raises(ValueError):
        _decode_meta(b"\xff\x01\x02", FLAG_BINMETA)   # unknown field id
    with pytest.raises(ValueError):
        _decode_meta(b"\x00\x01", FLAG_BINMETA)       # truncated i64
