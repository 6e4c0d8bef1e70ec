"""ctypes bindings for the native (C++) transport core.

The native core (native/transport.cc) is the C++ counterpart of the Python
van's socket layer — the role ZMQVan plays in the reference
(3rdparty/ps-lite/src/zmq_van.h:41-516). It owns the listener, per-
connection frame-parsing reader threads, the inbound frame queue, and the
per-destination connection cache; routing and message semantics stay in
Python (van.py). Both backends speak the identical wire format
(message.py), so native and pure-Python nodes interoperate in one job.

Who owns a buffer. Outbound, :meth:`NativeTransport.sendv` passes the
core the addresses of the caller's buffers (a ``Message``'s
``frame_parts()``); the core writes them as they lie and keeps nothing:
they must stay alive and unchanged until the call returns, which the
caller's references see to. Inbound, the core reads a frame once into a
``malloc``ed buffer and :meth:`NativeTransport.wait_frame` is handed
that buffer itself; :meth:`NativeTransport.take_frame` wraps it in a
read-only ``memoryview`` without copying. The frame is python's from
then on: every part ``Message.unpack`` slices from it, and every array
``np.frombuffer`` makes over a part, holds a reference to the view's
owner, and when the last of them is dropped a finaliser releases the
buffer through ``gx_free``, exactly once (``frames_freed()`` counts).

Selection: ``GEOMX_NATIVE_VAN=1`` (default when the library is buildable)
/ ``GEOMX_NATIVE_VAN=0`` forces pure Python. The shared library is built
on demand with g++ the first time it is needed and cached next to the
source under a name that carries the source's hash (native_lib.py).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
import weakref
from typing import Optional, Sequence

import numpy as np

from geomx_tpu.native_lib import ensure_built

log = logging.getLogger("geomx.native")

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_error: Optional[str] = None


def load_library() -> Optional[ctypes.CDLL]:
    """Load (building if necessary) the native transport library.

    Returns None — with the reason cached — when the library cannot be
    built/loaded; callers fall back to the pure-Python backend.
    """
    global _lib, _lib_error
    with _lib_lock:
        if _lib is not None or _lib_error is not None:
            return _lib
        try:
            lib = ctypes.CDLL(ensure_built("transport", ["-O2", "-pthread"]))
        except (OSError, subprocess.SubprocessError) as e:
            _lib_error = str(e)
            log.warning("native transport unavailable (%s); "
                        "using pure-Python van", e)
            return None
        lib.gx_create.restype = ctypes.c_void_p
        lib.gx_create.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.gx_port.restype = ctypes.c_int
        lib.gx_port.argtypes = [ctypes.c_void_p]
        lib.gx_set_route.restype = None
        lib.gx_set_route.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                     ctypes.c_char_p, ctypes.c_int]
        lib.gx_send.restype = ctypes.c_int64
        lib.gx_send.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                ctypes.c_char_p, ctypes.c_uint64]
        lib.gx_sendv.restype = ctypes.c_int64
        lib.gx_sendv.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                 ctypes.POINTER(ctypes.c_void_p),
                                 ctypes.POINTER(ctypes.c_uint64),
                                 ctypes.c_uint64]
        lib.gx_send_addr.restype = ctypes.c_int64
        lib.gx_send_addr.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_int, ctypes.c_char_p,
                                     ctypes.c_uint64]
        lib.gx_recv.restype = ctypes.c_int64
        lib.gx_recv.argtypes = [ctypes.c_void_p,
                                ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
                                ctypes.c_double]
        lib.gx_wait.restype = ctypes.c_int
        lib.gx_wait.argtypes = [ctypes.c_void_p, ctypes.c_double]
        lib.gx_free.restype = None
        lib.gx_free.argtypes = [ctypes.c_void_p]
        lib.gx_frames_freed.restype = ctypes.c_uint64
        lib.gx_frames_freed.argtypes = []
        lib.gx_send_bytes.restype = ctypes.c_uint64
        lib.gx_send_bytes.argtypes = [ctypes.c_void_p]
        lib.gx_recv_bytes.restype = ctypes.c_uint64
        lib.gx_recv_bytes.argtypes = [ctypes.c_void_p]
        lib.gx_stop.restype = None
        lib.gx_stop.argtypes = [ctypes.c_void_p]
        lib.gx_destroy.restype = None
        lib.gx_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return load_library() is not None


def enabled() -> bool:
    """Native backend selection: on by default when buildable."""
    flag = os.environ.get("GEOMX_NATIVE_VAN", "1")
    return flag not in ("0", "false", "no") and available()


def frames_freed() -> int:
    """Frames released through ``gx_free`` so far, process-wide."""
    lib = load_library()
    return int(lib.gx_frames_freed()) if lib is not None else 0


class NativeTransport:
    """One bound endpoint of the native core.

    API mirrors exactly what van.py needs: bind-at-construction,
    set_route/send per node id, one-shot send_to_addr, blocking recv of
    complete frames, byte counters, stop.
    """

    def __init__(self, bind_host: str, port: int = 0):
        lib = load_library()
        if lib is None:
            raise RuntimeError(f"native transport unavailable: {_lib_error}")
        self._lib = lib
        self._h = lib.gx_create(bind_host.encode(), port)
        if not self._h:
            raise OSError(f"native bind failed on {bind_host}:{port}")
        self.port: int = lib.gx_port(self._h)
        self._stopped = False

    def set_route(self, node_id: int, host: str, port: int) -> None:
        self._lib.gx_set_route(self._h, node_id, host.encode(), port)

    def send(self, node_id: int, frame: bytes) -> int:
        n = self._lib.gx_send(self._h, node_id, frame, len(frame))
        return self._sent(node_id, n)

    def sendv(self, node_id: int, buffers: Sequence) -> int:
        """One frame from ``buffers`` (any buffer objects, read-only
        ones too), written in order from where they lie: a gathered
        write, no joined copy. They are borrowed for the call only."""
        n = len(buffers)
        # an array over each buffer: its address, and a reference that
        # keeps the memory in place until the write has returned
        held = [np.frombuffer(b, np.uint8) for b in buffers]
        ptrs = (ctypes.c_void_p * n)(*[a.ctypes.data for a in held])
        lens = (ctypes.c_uint64 * n)(*[a.size for a in held])
        return self._sent(
            node_id, self._lib.gx_sendv(self._h, node_id, ptrs, lens, n))

    @staticmethod
    def _sent(node_id: int, n: int) -> int:
        if n == -2:
            raise OSError(f"no route to node {node_id}")
        if n < 0:
            raise OSError(f"native send to node {node_id} failed")
        return int(n)

    def send_to_addr(self, host: str, port: int, frame: bytes) -> None:
        n = self._lib.gx_send_addr(self._h, host.encode(), port,
                                   frame, len(frame))
        if n < 0:
            raise OSError(f"native send to {host}:{port} failed")

    def wait_begin(self, timeout_s: float = 1.0) -> bool:
        """Block until a frame has BEGUN to arrive (or lies complete)
        and has not been taken: True; False on timeout; raises on
        shutdown. The core goes on reading it on its own thread; the
        van opens ``van.recv`` here, so that the span covers the rest of
        the read, and then calls :meth:`wait_frame`."""
        n = self._lib.gx_wait(self._h, timeout_s)
        if n < 0:
            raise ConnectionAbortedError("native transport stopped")
        return bool(n)

    def wait_frame(self, timeout_s: float = 1.0):
        """Block for one complete frame: ``(address, length)`` of the
        buffer the core's reader filled, now the caller's (nothing was
        copied to hand it over), for :meth:`take_frame`; None on
        timeout; raises on shutdown."""
        out = ctypes.POINTER(ctypes.c_uint8)()
        n = self._lib.gx_recv(self._h, ctypes.byref(out), timeout_s)
        if n == -1:
            return None
        if n < 0:
            raise ConnectionAbortedError("native transport stopped")
        return ctypes.addressof(out.contents), n

    def take_frame(self, frame) -> memoryview:
        """A frame of :meth:`wait_frame` as a read-only byte view of
        that buffer. ``gx_free`` runs when the last reference to the
        view's owner goes (slices of the view and arrays over them each
        hold one)."""
        addr, n = frame
        owner = (ctypes.c_uint8 * n).from_address(addr)
        # not at interpreter exit: arrays may still look into the frame
        weakref.finalize(owner, self._lib.gx_free, addr).atexit = False
        return memoryview(owner).cast("B").toreadonly()

    def recv(self, timeout_s: float = 1.0) -> Optional[memoryview]:
        """One complete frame, or None on timeout; raises on shutdown."""
        frame = self.wait_frame(timeout_s)
        return None if frame is None else self.take_frame(frame)

    @property
    def send_bytes(self) -> int:
        return int(self._lib.gx_send_bytes(self._h))

    @property
    def recv_bytes(self) -> int:
        return int(self._lib.gx_recv_bytes(self._h))

    def stop(self) -> None:
        if not self._stopped:
            self._stopped = True
            self._lib.gx_stop(self._h)

    def close(self) -> None:
        self.stop()
        if self._h:
            self._lib.gx_destroy(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:
            pass
