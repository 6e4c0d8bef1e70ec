"""Who owns a frame's memory, and for how long.

Receive side: an array a handler gets is a read-only VIEW of the buffer
the socket filled; the frame lives while any array over it does and is
released (``gx_free``) when the last one goes. Send side: a part is
borrowed from the caller while the call that writes it is on the stack;
where the van keeps a message longer (the resender, the priority queue)
it takes its snapshot when it decides to, so what reaches the wire, or
comes out of a fault plan's ``delay`` / ``dup`` hold on the far side, is
the arrays' content at ``send()`` whatever the caller writes afterwards.
"""

import gc
import json
import threading
import weakref

import numpy as np
import pytest

from geomx_tpu import telemetry
from geomx_tpu.config import Config
from geomx_tpu.ps import native
from geomx_tpu.ps.kv_app import KVPairs, KVServer, KVWorker
from geomx_tpu.ps.message import Message, Meta
from tests.harness import (DEADLINES, _poll, count_sent_payload, make_tier,
                           shutdown)

needs_native = pytest.mark.skipif(
    not native.available(), reason="native transport not buildable")


@pytest.fixture
def counters():
    telemetry.reset()
    telemetry.enable(True)

    def read(name):
        snap = telemetry.snapshot()["counters"]
        return sum(v for k, v in snap.items() if k.startswith(name))

    yield read
    telemetry.reset()
    telemetry.enable(False)


@needs_native
def test_received_array_is_a_view_of_its_frame_and_frees_it_last():
    a = native.NativeTransport("127.0.0.1", 0)
    b = native.NativeTransport("127.0.0.1", 0)
    try:
        a.set_route(7, "127.0.0.1", b.port)
        vals = np.arange(4096, dtype=np.float32)
        idx = np.arange(4096, dtype=np.int32)[::-1].copy()
        m = Message(Meta(sender=1, recver=7))
        m.add_array(vals)
        m.add_array(idx)
        a.sendv(7, m.frame_parts())
        frame = b.take_frame(b.wait_frame(timeout_s=5.0))
        assert isinstance(frame, memoryview) and frame.readonly
        got = Message.unpack(frame)
        g_vals, g_idx = got.arrays()
        np.testing.assert_array_equal(g_vals, vals)
        np.testing.assert_array_equal(g_idx, idx)
        # views of ONE buffer, the one the socket filled; not writable
        whole = np.frombuffer(frame, np.uint8)
        for arr in (g_vals, g_idx):
            assert not arr.flags.writeable
            assert np.shares_memory(arr, whole)
            with pytest.raises(ValueError):
                arr[0] = 1
        owner = frame.obj
        assert got.data[0].obj is owner and got.data[1].obj is owner
        alive = weakref.ref(owner)
        freed = native.frames_freed()
        del whole, frame, got, g_vals, owner, arr
        gc.collect()
        # one array is left: the frame stays, whole
        assert alive() is not None
        np.testing.assert_array_equal(g_idx, idx)
        del g_idx
        gc.collect()
        assert alive() is None
        assert native.frames_freed() >= freed + 1
    finally:
        a.close()
        b.close()


@needs_native
def test_frames_are_freed_once_each_and_their_blocks_reused_safely():
    """The core keeps released blocks for the frames to come: a block is
    handed out again only after its frame's last view went, and a frame
    that is still held keeps its bytes whatever is read meanwhile."""
    a = native.NativeTransport("127.0.0.1", 0)
    b = native.NativeTransport("127.0.0.1", 0)
    try:
        a.set_route(7, "127.0.0.1", b.port)
        freed = native.frames_freed()
        owners = []
        held = None
        for i in range(20):
            n = (100, 300_000, 40_000)[i % 3]   # small, large, between
            m = Message(Meta(sender=1, recver=7, timestamp=i))
            m.add_array(np.full(n, i, np.int32))
            m.add_array(np.arange(n, dtype=np.float32))
            a.sendv(7, m.frame_parts())
            mv = b.recv(timeout_s=5.0)
            assert mv == m.pack()
            got = Message.unpack(mv)
            assert got.get_array(0)[0] == i and got.get_array(0)[-1] == i
            if i == 4:
                held = got.get_array(1)         # a large frame, kept
            else:
                owners.append(weakref.ref(mv.obj))
            del mv, got
        gc.collect()
        assert all(o() is None for o in owners)
        np.testing.assert_array_equal(
            held, np.arange(300_000, dtype=np.float32))
        _poll(lambda: native.frames_freed() >= freed + 19,
              "the nineteen frames to be released", 5.0)
        del held
        gc.collect()
        _poll(lambda: native.frames_freed() >= freed + 20,
              "the held frame to be released", 5.0)
    finally:
        a.close()
        b.close()


def test_snapshot_owns_what_it_keeps():
    arr = np.arange(8, dtype=np.float32)
    m = Message(Meta(recver=3))
    m.add_array(arr)
    assert isinstance(m.data[0], memoryview)
    assert m.borrowed_bytes() == 32
    before = m.pack()
    assert m.snapshot() == 32
    arr[:] = -1
    assert m.pack() == before           # the snapshot is the message's own
    assert m.borrowed_bytes() == 0 and m.snapshot() == 0


MODES = {
    # mode: (Config fields, deliveries a message makes)
    "plain": ({}, 1),
    "resender": ({"resend": True, "resend_timeout_ms": 60, "ps_seed": 7,
                  "fault_plan": json.dumps(
                      [{"type": "drop", "p": 0.4}])}, 1),
    "priority": ({"enable_p3": True}, 1),
    "delay": ({"fault_plan": json.dumps(
        [{"type": "delay", "delay_s": 0.15, "jitter_s": 0.05}]),
        "ps_seed": 3}, 1),
    "dup": ({"fault_plan": json.dumps([{"type": "dup"}])}, 2),
}


@pytest.mark.parametrize("mode", list(MODES))
def test_writes_after_send_do_not_reach_the_receiver(mode, counters,
                                                     monkeypatch):
    fields, deliveries = MODES[mode]
    sent_payload = count_sent_payload(monkeypatch)
    sched, servers, workers = make_tier(
        num_workers=1, num_servers=1, cfg=Config(**fields))
    n_msgs = 8
    seen = {}
    lock = threading.Lock()
    try:
        def handle(req, kvs, srv):
            # what the handler is given must be what was sent, and must
            # stay so while it is held (a delayed or duplicated delivery)
            assert not kvs.vals[0].flags.writeable or \
                servers[0].van.backend == "python"
            with lock:
                seen.setdefault(kvs.keys[0], []).append(
                    (kvs.vals[0].copy(), kvs.aux[0].copy()))
            srv.response(req)

        KVServer(servers[0]).set_request_handle(handle)
        kvw = KVWorker(workers[0])
        want = {}
        tss = []
        for k in range(n_msgs):
            vals = np.full(5000, float(k), np.float32)
            aux = np.arange(5000, dtype=np.int32) + k
            want[k] = (vals.copy(), aux.copy())
            tss.append(kvw.push(
                KVPairs(keys=[k], vals=[vals], aux=[aux], compr="bsc"),
                server_rank=0))
            # the caller's memory is the caller's again
            vals[:] = -1.0
            aux[:] = -1
        for ts in tss:
            kvw.wait(ts, DEADLINES["op_timeout_s"])
        _poll(lambda: all(len(seen.get(k, ())) >= deliveries
                          for k in range(n_msgs)),
              "every delivery", DEADLINES["op_timeout_s"])
    finally:
        shutdown(sched, *servers, *workers)
    for k in range(n_msgs):
        assert len(seen[k]) == deliveries
        for vals, aux in seen[k]:
            np.testing.assert_array_equal(vals, want[k][0])
            np.testing.assert_array_equal(aux, want[k][1])
    borrowed = counters("van.payload_bytes_borrowed")
    copied = counters("van.payload_bytes_copied")
    if mode in ("plain", "delay", "dup"):
        # written from the caller's memory, read as views of the frame:
        # once a side, and not one part copied
        assert copied == 0
        assert borrowed == 2 * sum(sent_payload)
    elif mode == "priority":
        # the queue keeps the message past send(): one snapshot each,
        # then the frame's views on the far side
        assert copied == sum(sent_payload) == borrowed
    else:
        # the resend table keeps it: a snapshot a message, however many
        # times the monitor writes it again
        assert copied == (40000 + 32) * n_msgs


@needs_native
@pytest.mark.parametrize("pad", range(4))
def test_native_frames_put_their_parts_where_numpy_reads_fast(pad):
    """The wire's layout is unpadded, so where a part falls depends on
    the meta's length; the core places the frame so that the first
    part's data is 16-byte aligned whatever that length is, and parts
    of 4-byte elements follow each other 4-aligned. An array over an
    unaligned part costs numpy several times a copy in every pass."""
    a = native.NativeTransport("127.0.0.1", 0)
    b = native.NativeTransport("127.0.0.1", 0)
    try:
        a.set_route(7, "127.0.0.1", b.port)
        for k in range(4):
            m = Message(Meta(sender=1, recver=7, body="x" * (pad + 4 * k)))
            m.add_array(np.arange(3, dtype=np.int64))
            m.add_array(np.arange(1001, dtype=np.float32))
            m.add_array(np.arange(1001, dtype=np.int32))
            a.sendv(7, m.frame_parts())
            frame = b.recv(timeout_s=5.0)
            assert frame == m.pack()            # the wire's bytes, unpadded
            got = Message.unpack(frame)
            first = np.frombuffer(got.data[0], np.uint8)
            assert first.ctypes.data % 16 == 0
            for i in (1, 2):
                arr = np.frombuffer(got.data[i], m.meta.dtypes[i])
                assert arr.flags.aligned
                np.testing.assert_array_equal(arr, m.get_array(i))
    finally:
        a.close()
        b.close()


def test_an_unaligned_part_is_copied_once_and_booked(counters):
    m = Message(Meta(recver=3))
    m.add_array(np.arange(3, dtype=np.uint8))       # an odd-sized part
    m.add_array(np.arange(64, dtype=np.float32))
    m.add_array(np.arange(5, dtype=np.int64))
    wire = m.pack()
    for shift in range(4):
        # wherever the frame lies, one of these starts is off by 1-3
        buf = bytes(shift) + wire
        got = Message.unpack(memoryview(buf)[shift:])
        before = counters("van.payload_bytes_copied")
        arr = got.get_array(1)
        raw = np.frombuffer(got.data[1], np.float32)
        assert arr.flags.aligned and not arr.flags.writeable
        np.testing.assert_array_equal(arr, np.arange(64, dtype=np.float32))
        copied = counters("van.payload_bytes_copied") - before
        assert copied == (0 if raw.flags.aligned else 256)
        assert np.shares_memory(arr, raw) == raw.flags.aligned
        assert got.get_ints(2) == [0, 1, 2, 3, 4]   # read where it lies
