"""DGT — Differential Gradient Transmission (block-differentiated QoS send).

Re-implements the reference's DGT (reference: 3rdparty/ps-lite/include/ps/
kv_app.h:966-1260 KVServer::Send block split + EvalMsgContribution +
Get_channel, src/van.cc:707-745 Classifier/Important_scheduler/
Unimportant_scheduler, van.cc:330-370 ProcessDataMsg reassembly,
van.cc:750-840 4-bit encode/decode) for the TPU framework's host-side WAN
hop:

- a large gradient push is split into blocks of ``DGT_BLOCK_SIZE`` elements;
- each block's *contribution* is an EWMA of its mean |grad|
  (``DGT_CONTRI_ALPHA``), tracked per (destination, key, block index);
- blocks are ranked by contribution; the top ``DMLC_K`` fraction — plus the
  tail block, which triggers reassembly — travel on channel 0 (reliable
  TCP, the "important" queue); the rest spread over channels 1..C:
  ENABLE_DGT=1 -> raw UDP datagrams (lossy, zero-filled if lost),
  ENABLE_DGT=2 -> TCP ("unimportant" queue, yields to important traffic),
  ENABLE_DGT=3 -> 4-bit quantized then TCP;
- ``tos`` carries the DSCP marking the reference sets ((C-channel)*32,
  kv_app.h:1101) — recorded in meta for parity/observability;
- the receiver reassembles per (sender, key, timestamp); blocks arriving
  after the tail completed the buffer are dropped (UDP stragglers), missing
  blocks stay zero — the loss-tolerance-by-design that makes DGT safe for
  gradients.

Wire note: block messages are full framed Messages (or UDP datagrams of the
same encoding) with ``meta.msg_type`` = BLOCK/TAIL; the tail carries the
original message's non-value data parts (keys/offsets/totals/lens) so the
reassembled message is indistinguishable from a normal push upstream.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from geomx_tpu import telemetry
from geomx_tpu.ps.message import Message, Meta

log = logging.getLogger("geomx.dgt")

MSG_TYPE_BLOCK = 1
MSG_TYPE_TAIL = 2

# UDP datagrams must stay under the practical 64KB limit
MAX_UDP_PAYLOAD = 60000


def quantize4(arr: np.ndarray) -> Tuple[np.ndarray, float]:
    """4-bit signed quantization (reference: van.cc:750-793 encode).

    Per-buffer max-|v| scaling onto integer levels [-7, 7]; two codes per
    byte. Returns (packed bytes, scale).
    """
    arr = np.asarray(arr, dtype=np.float32).ravel()
    scale = float(np.max(np.abs(arr))) if arr.size else 0.0
    if scale == 0.0:
        codes = np.zeros(arr.size, dtype=np.int8)
    else:
        codes = np.clip(np.rint(arr / scale * 7.0), -7, 7).astype(np.int8)
    u = (codes & 0x0F).astype(np.uint8)          # two's-complement nibbles
    if u.size % 2:
        u = np.concatenate([u, np.zeros(1, np.uint8)])
    packed = (u[0::2] | (u[1::2] << 4)).astype(np.uint8)
    return packed, scale


def dequantize4(packed: np.ndarray, n: int, scale: float) -> np.ndarray:
    """Inverse of :func:`quantize4` (reference: van.cc:794-840 decode)."""
    packed = np.asarray(packed, dtype=np.uint8)
    lo = (packed & 0x0F).astype(np.int8)
    hi = ((packed >> 4) & 0x0F).astype(np.int8)
    # sign-extend 4-bit two's complement
    lo = np.where(lo > 7, lo - 16, lo)
    hi = np.where(hi > 7, hi - 16, hi)
    codes = np.empty(packed.size * 2, dtype=np.int8)
    codes[0::2] = lo
    codes[1::2] = hi
    return codes[:n].astype(np.float32) / 7.0 * scale


class DGTSender:
    """Splits one KV push into channelized block messages."""

    def __init__(self, mode: int, num_channels: int, block_size: int,
                 contri_alpha: float, k: float, k_min: float,
                 adaptive_k: bool):
        self.mode = mode                      # ENABLE_DGT in {1,2,3}
        self.num_channels = max(num_channels, 1)
        self.block_size = max(block_size, 1)
        self.alpha = contri_alpha
        self.k = k
        self.k_min = k_min
        self.adaptive_k = adaptive_k
        # (dest, key, block_idx) -> EWMA contribution
        self._contri: Dict[Tuple[int, int, int], float] = {}
        self._lock = threading.Lock()
        self._iters = 0

    def applicable(self, msg: Message) -> bool:
        """DGT applies to plain (uncompressed) single-key data pushes large
        enough to split (reference gates on kDefaultPushPull && push,
        kv_app.h:1146)."""
        m = msg.meta
        if not (m.push and m.request) or m.simple_app or m.compr:
            return False
        if len(msg.data) != 5:                # keys/offs/tots/lens/val
            return False
        val_elems = int(np.prod(msg.meta.shapes[4])) if msg.meta.shapes else 0
        return val_elems > self.block_size

    def current_k(self) -> float:
        """Reliable fraction; ADAPTIVE_K_FLAG ramps k_min -> k over the
        first epochs (reference: kv_app.h:1080-1092 adaptive p)."""
        if not self.adaptive_k:
            return self.k
        ramp = min(self._iters / 100.0, 1.0)
        return self.k_min + (self.k - self.k_min) * ramp

    def split(self, msg: Message) -> List[Tuple[int, Message]]:
        """-> [(channel, block_message)]; channel 0 = reliable/important."""
        meta = msg.meta
        val = msg.get_array(4)
        flat = np.ascontiguousarray(val).ravel()
        n = flat.size
        key = meta.key if meta.key >= 0 else int(msg.get_array(0)[0])
        bs = self.block_size
        # UDP datagram cap: shrink blocks so a packed frame fits
        if self.mode == 1:
            bs = min(bs, MAX_UDP_PAYLOAD // max(flat.dtype.itemsize, 1))
        nblocks = (n + bs - 1) // bs
        self._iters += 1

        # contribution EWMA per block (reference: EvalMsgContribution)
        contris = np.empty(nblocks, np.float64)
        with self._lock:
            for i in range(nblocks):
                blk = flat[i * bs:(i + 1) * bs]
                mean_abs = float(np.mean(np.abs(blk))) if blk.size else 0.0
                ck = (meta.recver, key, i)
                prev = self._contri.get(ck, mean_abs)
                cur = self.alpha * prev + (1.0 - self.alpha) * mean_abs
                self._contri[ck] = cur
                contris[i] = cur

        # rank: top ceil(k * nblocks) -> channel 0; tail block forced to 0
        # (reference: Get_channel kv_app.h:1000 + tail at 1098)
        order = np.argsort(-contris, kind="stable")
        n_reliable = max(int(np.ceil(self.current_k() * nblocks)), 1)
        channel_of = np.empty(nblocks, np.int32)
        spread = max(self.num_channels, 1)
        for rank, i in enumerate(order):
            if rank < n_reliable:
                channel_of[i] = 0
            else:
                channel_of[i] = 1 + (rank - n_reliable) % spread
        channel_of[nblocks - 1] = 0

        out: List[Tuple[int, Message]] = []
        for i in range(nblocks):
            blk = flat[i * bs:(i + 1) * bs]
            ch = int(channel_of[i])
            is_tail = i == nblocks - 1
            bmeta = dataclasses.replace(
                meta,
                dtypes=[], shapes=[],
                msg_type=MSG_TYPE_TAIL if is_tail else MSG_TYPE_BLOCK,
                first_key=key,
                seq=i, seq_begin=0, seq_end=nblocks - 1,
                val_bytes=bs * flat.dtype.itemsize,   # nominal block stride
                total_bytes=n * flat.dtype.itemsize,
                channel=ch,
                tos=(self.num_channels - ch) * 32 if ch else 0,
                lossy=self.mode == 1,
            )
            bmsg = Message(meta=bmeta)
            if is_tail:
                # tail carries the original header parts + its own block so
                # the receiver can rebuild a full KV message
                for j in range(4):
                    bmsg.meta.dtypes.append(meta.dtypes[j])
                    bmsg.meta.shapes.append(meta.shapes[j])
                    bmsg.data.append(msg.data[j])
                bmsg.meta.val_dtype = flat.dtype.str
                bmsg.add_array(blk)
            elif ch > 0 and self.mode == 3:
                packed, scale = quantize4(blk)
                bmsg.meta.compr = "dgt4"
                bmsg.meta.dgt_scale = scale
                bmsg.meta.dgt_n = blk.size
                bmsg.meta.val_dtype = flat.dtype.str
                bmsg.add_array(packed)
            else:
                bmsg.meta.val_dtype = flat.dtype.str
                bmsg.add_array(blk)
            out.append((ch, bmsg))
        return out


class _Group:
    __slots__ = ("blocks", "tail_msg", "timer")

    def __init__(self):
        self.blocks: Dict[int, np.ndarray] = {}
        self.tail_msg: Optional[Message] = None
        self.timer: Optional[threading.Timer] = None


class DGTReassembler:
    """Receiver side: rebuild the original push from block messages
    (reference: ProcessDataMsg msg_map, van.cc:330-370).

    Divergence from the reference (deliberate improvement): the reference
    zero-fills the instant the tail arrives — but the tail rides the
    *important* queue, which drains before the unimportant queue even
    starts, so on a fast network lossy blocks would ALWAYS be "lost". We
    instead arm a short grace timer when the tail arrives incomplete:
    stragglers landing within ``grace_s`` complete the gradient exactly;
    only blocks truly lost (or slower than the grace window) zero-fill.
    """

    def __init__(self, grace_s: float = 0.1,
                 deliver: Optional[Callable[[Message], None]] = None):
        self.grace_s = grace_s
        self.deliver = deliver         # set by the van before use
        self._lock = threading.Lock()
        # (sender, key, timestamp) -> _Group
        self._pending: Dict[Tuple[int, int, int], _Group] = {}
        # recently-completed groups: drop stragglers past the grace window
        self._done: Dict[Tuple[int, int, int], int] = {}
        self.blocks_received = 0
        self.blocks_dropped_late = 0
        self.groups_zero_filled = 0

    @staticmethod
    def _block_array(msg: Message) -> np.ndarray:
        part = msg.data[-1]
        dt = np.dtype(msg.meta.val_dtype or "<f4")
        if msg.meta.compr == "dgt4":
            packed = np.frombuffer(part, dtype=np.uint8)
            return dequantize4(packed, msg.meta.dgt_n,
                               msg.meta.dgt_scale).astype(dt)
        return np.frombuffer(part, dtype=dt)

    def accept(self, msg: Message) -> Optional[Message]:
        """Feed one block. Returns the reassembled Message when the group
        is complete; an incomplete group whose tail has arrived is
        delivered via ``self.deliver`` when the grace timer fires."""
        meta = msg.meta
        gk = (meta.sender, meta.first_key, meta.timestamp)
        blk = self._block_array(msg)
        with self._lock:
            self.blocks_received += 1
            if gk in self._done:
                self.blocks_dropped_late += 1
                return None
            group = self._pending.setdefault(gk, _Group())
            # duplicate seq = network duplicate (UDP may duplicate): keep
            # the first copy. (The reference merges additively, MergeMsg —
            # correct there because its duplicates are partial aggregates
            # from distinct senders; within one (sender,key,ts) group a
            # repeat can only be a dupe, and adding would double-count.)
            group.blocks.setdefault(meta.seq, blk)
            if meta.msg_type == MSG_TYPE_TAIL:
                group.tail_msg = msg
            if group.tail_msg is None:
                return None
            complete = len(group.blocks) >= meta.seq_end + 1
            if not complete:
                if not meta.lossy:
                    # reliable modes (ENABLE_DGT=2/3): every block rides
                    # TCP and WILL arrive — never zero-fill, just wait
                    return None
                if group.timer is None and self.deliver is not None:
                    group.timer = threading.Timer(
                        self.grace_s, self._grace_expired, (gk,))
                    group.timer.daemon = True
                    group.timer.start()
                    return None
                if group.timer is not None:
                    return None     # timer already armed; wait for it
                # no deliver hook (unit-test mode): zero-fill immediately
            if group.timer is not None:
                group.timer.cancel()
            self._finish(gk)
        return self._assemble(group)

    def _grace_expired(self, gk) -> None:
        with self._lock:
            group = self._pending.get(gk)
            if group is None or group.tail_msg is None:
                return
            self.groups_zero_filled += 1
            self._finish(gk)
        out = self._assemble(group)
        if self.deliver is not None:
            self.deliver(out)

    def _finish(self, gk) -> None:
        """Must hold the lock: move a group to the done set."""
        self._pending.pop(gk, None)
        self._done[gk] = 1
        if len(self._done) > 4096:
            self._done.pop(next(iter(self._done)))

    def _assemble(self, group: _Group) -> Message:
        meta = group.tail_msg.meta
        dt = np.dtype(meta.val_dtype or "<f4")
        itemsize = dt.itemsize
        total_elems = meta.total_bytes // itemsize
        stride = max(meta.val_bytes // itemsize, 1)
        buf = np.zeros(total_elems, dtype=dt)
        for seq, arr in group.blocks.items():
            off = seq * stride
            buf[off:off + arr.size] = arr[:max(total_elems - off, 0)]
        # the blocks' parts joined into one value: a copy of each
        telemetry.counter_inc("van.payload_bytes_copied", buf.nbytes)

        out_meta = dataclasses.replace(
            meta, msg_type=0, seq=-1, seq_begin=-1, seq_end=-1,
            first_key=-1, val_bytes=0, total_bytes=0, channel=0, tos=0,
            compr="", dgt_scale=0.0, dgt_n=0, val_dtype="",
            # keep only the 4 header-part entries; add_array appends the
            # reassembled value's own dtype/shape
            dtypes=list(meta.dtypes[:4]), shapes=list(meta.shapes[:4]),
        )
        out = Message(meta=out_meta, data=list(group.tail_msg.data[:4]))
        out.add_array(buf)
        return out


class DGTQueues:
    """Important/unimportant send queues with two scheduler threads
    (reference: van.cc:707-745). The unimportant sender only proceeds when
    the important queue is empty."""

    def __init__(self, send_fn: Callable[[int, Message], None],
                 send_udp_fn: Optional[Callable[[int, int, Message], None]],
                 mode: int):
        self._send = send_fn
        self._send_udp = send_udp_fn
        self.mode = mode
        self._imp: List[Tuple[int, Message]] = []
        self._unimp: List[Tuple[int, int, Message]] = []
        self._cv = threading.Condition()
        self._stop = False
        self._threads = [
            threading.Thread(target=self._important_loop,
                             name="dgt-important", daemon=True),
            threading.Thread(target=self._unimportant_loop,
                             name="dgt-unimportant", daemon=True),
        ]
        for t in self._threads:
            t.start()

    def put(self, channel: int, target: int, msg: Message) -> None:
        with self._cv:
            if channel == 0:
                self._imp.append((target, msg))
            else:
                self._unimp.append((channel, target, msg))
            self._cv.notify_all()

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()

    def _important_loop(self) -> None:
        while True:
            with self._cv:
                while not self._imp and not self._stop:
                    self._cv.wait(0.5)
                if self._stop and not self._imp:
                    return
                target, msg = self._imp.pop(0)
            try:
                self._send(target, msg)
            except OSError as e:
                log.warning("DGT important send to %d failed: %s", target, e)

    def _unimportant_loop(self) -> None:
        while True:
            with self._cv:
                while (not self._unimp or self._imp) and not self._stop:
                    self._cv.wait(0.05)
                if self._stop and not self._unimp:
                    return
                if self._imp:        # re-check: important traffic first
                    continue
                channel, target, msg = self._unimp.pop(0)
            try:
                if self.mode == 1 and self._send_udp is not None:
                    self._send_udp(channel, target, msg)
                else:
                    self._send(target, msg)
            except OSError as e:
                # lossy by design: UDP failures are dropped silently,
                # TCP modes log (reference drops UDP losses too)
                if self.mode != 1:
                    log.warning("DGT unimportant send to %d failed: %s",
                                target, e)
