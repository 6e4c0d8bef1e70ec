"""DGT tests: 4-bit codec, block split/reassembly, loss tolerance, and the
full HiPS topology with ENABLE_DGT (reference: kv_app.h:966-1260 send path,
van.cc:330-370 reassembly, van.cc:707-745 classifier)."""

import numpy as np
import pytest

from geomx_tpu.ps import dgt
from geomx_tpu.ps.kv_app import KVPairs, _pack_kv
from geomx_tpu.ps.message import Message, Meta


def test_quantize4_roundtrip():
    rng = np.random.RandomState(0)
    x = rng.randn(1001).astype(np.float32)
    packed, scale = dgt.quantize4(x)
    assert packed.dtype == np.uint8 and packed.size == 501
    back = dgt.dequantize4(packed, x.size, scale)
    # 4-bit: 15 levels over [-max, max] -> error <= scale/7/2 + rounding
    assert np.max(np.abs(back - x)) <= scale / 7.0
    # zeros stay zeros
    p0, s0 = dgt.quantize4(np.zeros(8, np.float32))
    np.testing.assert_array_equal(dgt.dequantize4(p0, 8, s0), np.zeros(8))


def _push_msg(key=3, n=100, dtype=np.float32, seed=1, ts=7):
    rng = np.random.RandomState(seed)
    val = rng.randn(n).astype(dtype)
    kvs = KVPairs(keys=[key], vals=[val], offsets=[0], totals=[n], lens=[n])
    meta = Meta(recver=8, app_id=0, customer_id=0, timestamp=ts,
                request=True, push=True)
    msg = _pack_kv(meta, kvs)
    msg.meta.sender = 9
    return msg, val


def _mk_sender(mode=2, channels=2, block=16, k=0.5):
    return dgt.DGTSender(mode=mode, num_channels=channels, block_size=block,
                         contri_alpha=0.3, k=k, k_min=0.1, adaptive_k=False)


def test_split_reassemble_exact_tcp_mode():
    sender = _mk_sender(mode=2)
    msg, val = _push_msg(n=100)
    assert sender.applicable(msg)
    blocks = sender.split(msg)
    assert len(blocks) == 7  # ceil(100/16)
    # tail is channel 0 and carries the header parts
    tail = blocks[-1][1]
    assert tail.meta.msg_type == dgt.MSG_TYPE_TAIL
    assert blocks[-1][0] == 0
    assert len(tail.data) == 5
    # reliable fraction: ceil(0.5*7)=4 blocks on channel 0 (+ tail forced)
    assert sum(1 for ch, _ in blocks if ch == 0) >= 4

    reasm = dgt.DGTReassembler()
    out = None
    for _ch, b in blocks:
        # survive a pack/unpack cycle (what the wire does)
        b2 = Message.unpack(b.pack())
        b2.meta.sender = 9
        got = reasm.accept(b2)
        if got is not None:
            out = got
    assert out is not None
    np.testing.assert_array_equal(out.get_array(4), val)
    assert out.meta.push and out.meta.request and out.meta.timestamp == 7
    assert [int(x) for x in out.get_array(0)] == [3]
    assert out.meta.msg_type == 0


def test_reassemble_zero_fills_lost_blocks():
    sender = _mk_sender(mode=1, block=16, k=0.3)
    msg, val = _push_msg(n=100)
    blocks = sender.split(msg)
    reasm = dgt.DGTReassembler()
    lost = [i for i, (ch, _b) in enumerate(blocks) if ch > 0][:2]
    out = None
    for i, (_ch, b) in enumerate(blocks):
        if i in lost:
            continue
        got = reasm.accept(b)
        if got is not None:
            out = got
    assert out is not None
    rebuilt = out.get_array(4)
    stride = 16
    for i in range(len(blocks)):
        lo, hi = i * stride, min((i + 1) * stride, 100)
        if i in lost:
            np.testing.assert_array_equal(rebuilt[lo:hi], 0.0)
        else:
            np.testing.assert_array_equal(rebuilt[lo:hi], val[lo:hi])
    # straggler after completion is dropped, not re-delivered
    assert reasm.accept(blocks[lost[0]][1]) is None
    assert reasm.blocks_dropped_late == 1


def test_split_mode3_quantizes_unimportant():
    sender = _mk_sender(mode=3, block=16, k=0.3)
    msg, val = _push_msg(n=128)
    blocks = sender.split(msg)
    comprs = {b.meta.compr for ch, b in blocks if ch > 0}
    assert comprs == {"dgt4"}
    reasm = dgt.DGTReassembler()
    out = None
    for _ch, b in blocks:
        got = reasm.accept(Message.unpack(b.pack()))
        if got is not None:
            out = got
    rebuilt = out.get_array(4)
    # reliable blocks exact, quantized blocks within 4-bit error
    assert np.max(np.abs(rebuilt - val)) <= np.max(np.abs(val)) / 7.0 + 1e-6
    exact = [ch == 0 for ch, _ in blocks]
    for i, ex in enumerate(exact[:-1]):
        lo, hi = i * 16, (i + 1) * 16
        if ex:
            np.testing.assert_array_equal(rebuilt[lo:hi], val[lo:hi])


def test_contribution_ewma_prefers_hot_blocks():
    sender = _mk_sender(mode=2, block=10, k=0.26)
    key_msg = None
    for _ in range(5):
        # block 2 (elements 20-30) consistently has the largest gradient
        val = np.ones(100, np.float32) * 0.01
        val[20:30] = 5.0
        kvs = KVPairs(keys=[1], vals=[val], offsets=[0], totals=[100],
                      lens=[100])
        meta = Meta(recver=8, timestamp=1, request=True, push=True)
        key_msg = _pack_kv(meta, kvs)
        blocks = sender.split(key_msg)
    chans = [ch for ch, _ in blocks]
    assert chans[2] == 0           # hot block rides the reliable channel
    # ceil(0.26*10)=3 reliable + forced tail
    assert sum(1 for c in chans if c == 0) == 4


def test_not_applicable_cases():
    sender = _mk_sender()
    small, _ = _push_msg(n=8)      # smaller than one block
    assert not sender.applicable(small)
    msg, _ = _push_msg(n=100)
    msg.meta.push = False
    msg.meta.pull = True
    assert not sender.applicable(msg)
    c, _ = _push_msg(n=100)
    c.meta.compr = "bsc"
    assert not sender.applicable(c)


@pytest.mark.parametrize("mode", [1, 2, 3])
def test_hips_training_with_dgt(mode):
    """Full 2-party topology with ENABLE_DGT on the global tier. Modes 1/2
    are lossless on loopback (UDP rarely drops locally; zero-fill would
    only perturb, not break); mode 3 quantizes unimportant blocks, so we
    assert approximate convergence of the stored weights."""
    from tests.harness import Topology, _parallel
    from geomx_tpu.optimizer import SGD

    topo = Topology()
    # enable DGT on every node config (only global-tier vans act on it)
    base_common = topo._common

    def common_with_dgt(**kw):
        cfg = base_common(**kw)
        cfg.enable_dgt = mode
        cfg.udp_channel_num = 2
        cfg.dgt_block_size = 8
        cfg.dmlc_k = 0.5
        return cfg

    topo._common = common_with_dgt
    topo.start(sync_global=True)
    try:
        topo.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.arange(64, dtype=np.float32)
        _parallel([lambda kv=kv: kv.init(0, w0)
                   for kv in topo.workers + [topo.master]])

        def train(kv):
            kv.push(0, np.ones(64, np.float32))
            out = np.zeros(64, np.float32)
            kv.pull(0, out=out)
            kv.wait()
            if mode == 3:
                # unimportant blocks 4-bit quantized: small per-element error
                np.testing.assert_allclose(out, w0 - 4.0, atol=0.6)
            else:
                np.testing.assert_allclose(out, w0 - 4.0)

        _parallel([lambda kv=kv: train(kv) for kv in topo.workers])
    finally:
        topo.stop()


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
