"""The bytes a frame puts on the wire, and both backends reading them.

A van writes a message as a gathered write of the buffers it is given
(``Message.frame_parts``: the native core's ``gx_sendv``, the python
van's ``socket.sendmsg``); no joined frame exists on the data path. The
wire format did not change by a byte: what a plain TCP sink reads equals
the frame built the old way (``tobytes`` a part, one join), for control,
dense, ``bsc`` and ``bsc16`` messages, from either backend; and a native
and a python van still read each other's frames, both directions.
"""

import json
import socket
import struct
import threading

import numpy as np
import pytest

from geomx_tpu.ps import native
from geomx_tpu.ps.kv_app import (KVPairs, KVServer, KVWorker, _pack_kv,
                                 _unpack_kv)
from geomx_tpu.ps.message import (FLAG_BINMETA, FLAG_GLOBAL, MAGIC, Control,
                                  Message, Meta, Node, Role,
                                  _encode_meta_bin)
from geomx_tpu.ps.postoffice import Postoffice
from geomx_tpu.ps.van import Van
from geomx_tpu.simulate import free_port
from tests.harness import DEADLINES, _parallel, shutdown

BACKENDS = ["python"] + (["native"] if native.available() else [])
RNG = np.random.default_rng(35)


def _kv_message(kind: str) -> Message:
    meta = Meta(recver=8, sender=9, app_id=0, timestamp=11, request=True,
                push=True, pull=True, priority=-3, trace_round=4,
                trace_chunk=1)
    if kind == "dense":
        kvs = KVPairs(keys=[1, 2, 3],
                      vals=[RNG.standard_normal((3, 4)).astype(np.float32),
                            np.zeros(0, np.float32),
                            # not contiguous: the one part that is copied
                            RNG.standard_normal(64).astype(np.float32)[::2]],
                      offsets=[0, 0, 0], totals=[12, 0, 32],
                      lens=[12, 0, 32])
    elif kind in ("bsc", "bsc16"):
        vdt = np.float32 if kind == "bsc" else np.float16
        kvs = KVPairs(keys=[5, 6],
                      vals=[RNG.standard_normal(7).astype(vdt),
                            RNG.standard_normal(2).astype(vdt)],
                      aux=[np.arange(7, dtype=np.int32) * 3,
                           np.array([1, 40], np.int32)],
                      offsets=[0, 16], totals=[100, 64], lens=[100, 48],
                      compr=kind)
    elif kind == "many_parts":
        # 4 + 2 * 700 parts: more buffers than one sendmsg takes, and
        # enough bytes for a short write
        n = 700
        kvs = KVPairs(keys=list(range(n)),
                      vals=[RNG.standard_normal(1500).astype(np.float32)
                            for _ in range(n)],
                      aux=[np.arange(1500, dtype=np.int32)
                           for _ in range(n)],
                      compr="bsc")
    else:
        raise AssertionError(kind)
    return _pack_kv(meta, kvs)


def _message(kind: str) -> Message:
    if kind == "control":
        return Message(Meta(recver=8, sender=9, control_cmd=Control.BARRIER,
                            barrier_group=7, request=True))
    if kind == "control_nodes":
        return Message(Meta(
            recver=8, control_cmd=Control.ADD_NODE,
            nodes=[Node(role=Role.WORKER, hostname="10.0.0.7", port=1234)]))
    return _kv_message(kind)


def _parent_frame(msg: Message) -> bytes:
    """The frame as the tree before this test's PR built it: every part
    ``tobytes()``, one join (ps/message.py's docstring is the format)."""
    flags = FLAG_GLOBAL if msg.meta.is_global else 0
    if msg.meta.nodes:
        meta_b = json.dumps(msg.meta.to_dict(),
                            separators=(",", ":")).encode()
    else:
        meta_b = _encode_meta_bin(msg.meta)
        flags |= FLAG_BINMETA
    out = [struct.pack("<IiBiI", MAGIC, msg.meta.recver, flags,
                       msg.meta.priority, len(meta_b)),
           meta_b, struct.pack("<I", len(msg.data))]
    for arr in msg.arrays():
        b = np.ascontiguousarray(arr).tobytes()
        out += [struct.pack("<I", len(b)), b]
    return b"".join(out)


def _bare_van(backend: str) -> Van:
    van = Van(my_role=Role.WORKER, is_global=False, root_uri="127.0.0.1",
              root_port=free_port(), num_workers=1, num_servers=1)
    van.use_native = backend == "native"
    van._bind()
    van.my_id = 9
    assert van.backend == backend
    return van


KINDS = ["control", "control_nodes", "dense", "bsc", "bsc16", "many_parts"]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kind", KINDS)
def test_gathered_write_is_the_parent_frame(kind, backend):
    msg = _message(kind)
    golden = _parent_frame(msg)
    assert msg.pack() == golden
    assert b"".join(bytes(b) for b in msg.frame_parts()) == golden

    sink = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sink.bind(("127.0.0.1", 0))
    sink.listen(1)
    got = bytearray()

    def read():
        conn, _ = sink.accept()
        conn.settimeout(DEADLINES["op_timeout_s"])
        while len(got) < len(golden):
            chunk = conn.recv(1 << 16)
            if not chunk:
                break
            got.extend(chunk)
        conn.close()

    t = threading.Thread(target=read, daemon=True)
    t.start()
    van = _bare_van(backend)
    try:
        van.node_table[8] = ("127.0.0.1", sink.getsockname()[1])
        n = van._send_one_inner(8, msg)
        t.join(DEADLINES["op_timeout_s"])
    finally:
        van.stop()
        sink.close()
    assert n == len(golden)
    assert bytes(got) == golden  # byte for byte what the parent wrote


@pytest.mark.parametrize("server_backend", BACKENDS)
@pytest.mark.parametrize("worker_backend", BACKENDS)
@pytest.mark.parametrize("kind", ["dense", "bsc", "bsc16"])
def test_backends_read_each_others_frames(kind, worker_backend,
                                          server_backend):
    """Request one way, the echo the other, across the two backends."""
    kw = dict(is_global=False, root_uri="127.0.0.1", root_port=free_port(),
              num_workers=1, num_servers=1)
    sched = Postoffice(my_role=Role.SCHEDULER, **kw)
    server = Postoffice(my_role=Role.SERVER, **kw)
    worker = Postoffice(my_role=Role.WORKER, **kw)
    server.van.use_native = server_backend == "native"
    worker.van.use_native = worker_backend == "native"
    _parallel([lambda po=po: po.start(DEADLINES["start_s"])
               for po in (sched, server, worker)])
    try:
        assert server.van.backend == server_backend
        assert worker.van.backend == worker_backend
        seen = []

        def handle(req, kvs, srv):
            seen.append(kvs)
            srv.response(req, kvs)  # the received views go out again

        KVServer(server).set_request_handle(handle)
        kvw = KVWorker(worker)
        sent = _kv_message(kind)
        kvs = _unpack_kv(sent)
        ts = kvw.push(kvs, server_rank=0, pull=True)
        kvw.wait(ts, DEADLINES["op_timeout_s"])
        (echo,) = kvw.take_response(ts)
        for got in (seen[0], echo):
            assert got.keys == kvs.keys and got.compr == kvs.compr
            assert got.offsets == kvs.offsets and got.totals == kvs.totals
            for a, b in zip(got.vals, kvs.vals):
                assert a.dtype == b.dtype
                np.testing.assert_array_equal(a, b)
            for a, b in zip(got.aux, kvs.aux):
                if b is None:
                    assert a is None
                else:
                    assert a.dtype == b.dtype
                    np.testing.assert_array_equal(a, b)
    finally:
        shutdown(sched, server, worker)
