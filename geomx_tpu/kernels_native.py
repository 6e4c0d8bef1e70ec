"""ctypes bindings for the native aggregation/optimizer kernels.

Counterpart of the reference's C++ server math (reference:
kvstore_dist_server.h:1296 ``merged += recved`` runs as engine-scheduled
elemwise kernels; optimizer steps are C++ for built-ins). numpy holds the
GIL for these op sizes, so the per-key-locked server still serializes on
math; ctypes releases the GIL for the call's duration, restoring thread
scaling.

Same build-on-demand as ps/native.py (native_lib.ensure_built).
Disable with GEOMX_NATIVE_KERNELS=0; everything falls back to numpy.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from geomx_tpu.native_lib import ensure_built

log = logging.getLogger("geomx.kernels")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_failed = False

_f32p = ctypes.POINTER(ctypes.c_float)
_i64p = ctypes.POINTER(ctypes.c_int64)
_i32p = ctypes.POINTER(ctypes.c_int32)
_u8p = ctypes.POINTER(ctypes.c_uint8)


def enabled() -> bool:
    return os.environ.get("GEOMX_NATIVE_KERNELS", "1") not in ("0", "false")


def lib() -> Optional[ctypes.CDLL]:
    global _lib, _failed
    if _lib is not None:
        return _lib
    if _failed or not enabled():
        return None
    with _lock:
        if _lib is not None or _failed:
            return _lib
        try:
            L = ctypes.CDLL(ensure_built(
                "kernels", ["-O3", "-ffp-contract=off"]))
        except (OSError, subprocess.SubprocessError) as e:
            _failed = True
            log.warning("native kernels unavailable (%s); using numpy", e)
            return None
        i64 = ctypes.c_int64
        f32 = ctypes.c_float
        L.gxk_acc.restype = None
        L.gxk_acc.argtypes = [_f32p, _f32p, i64]
        L.gxk_copy.restype = None
        L.gxk_copy.argtypes = [_f32p, _f32p, i64]
        L.gxk_scale_acc.restype = None
        L.gxk_scale_acc.argtypes = [_f32p, f32, _f32p, i64]
        L.gxk_sgd.restype = None
        L.gxk_sgd.argtypes = [_f32p, _f32p, _f32p, f32, f32, f32, i64]
        L.gxk_adam.restype = None
        L.gxk_adam.argtypes = [_f32p, _f32p, _f32p, _f32p, f32, f32, f32,
                               f32, f32, i64, i64]
        L.gxk_bsc_sample.restype = None
        L.gxk_bsc_sample.argtypes = [_f32p, _f32p, f32, _i64p, _f32p, i64,
                                     _i64p, i64, _f32p]
        L.gxk_bsc_sweep.restype = i64
        L.gxk_bsc_sweep.argtypes = [_f32p, _f32p, i64, f32, _i64p, _f32p,
                                    i64, f32, _i32p, _f32p, i64]
        L.gxk_idx_encode.restype = i64
        L.gxk_idx_encode.argtypes = [ctypes.c_void_p, i64, ctypes.c_int,
                                     _u8p, i64]
        L.gxk_idx_decode.restype = i64
        L.gxk_idx_decode.argtypes = [_u8p, i64, i64, i64, ctypes.c_int,
                                     ctypes.c_void_p]
        L.gxk_entries_merge.restype = i64
        L.gxk_entries_merge.argtypes = [
            ctypes.c_void_p, _f32p, i64, ctypes.c_void_p, _f32p, i64,
            ctypes.c_int, ctypes.c_void_p, _f32p]
        _lib = L
        return _lib


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(_f32p)


def _eligible(*arrays) -> bool:
    return all(a.dtype == np.float32 and a.flags.c_contiguous
               for a in arrays)


# arrays below ~16k elements: the ctypes call overhead beats the GIL win
MIN_N = 16_384


def usable(n: int) -> bool:
    """Cheap pre-check so callers can skip preparatory copies when the
    native path will reject anyway (small array or no library)."""
    return n >= MIN_N and lib() is not None


def acc(dst: np.ndarray, src: np.ndarray) -> bool:
    """dst += src natively; False -> caller should use numpy."""
    L = lib()
    if L is None or dst.size < MIN_N or not _eligible(dst, src):
        return False
    L.gxk_acc(_ptr(dst), _ptr(src), dst.size)
    return True


def sgd(w: np.ndarray, g: np.ndarray, mom: Optional[np.ndarray],
        lr: float, momentum: float, wd: float) -> bool:
    L = lib()
    if L is None or w.size < MIN_N or not _eligible(
            w, g, *( [mom] if mom is not None else [] )):
        return False
    L.gxk_sgd(_ptr(w), _ptr(g), _ptr(mom) if mom is not None else None,
              lr, momentum, wd, w.size)
    return True


def adam(w: np.ndarray, g: np.ndarray, m: np.ndarray, v: np.ndarray,
         lr: float, b1: float, b2: float, eps: float, wd: float,
         t: int) -> bool:
    L = lib()
    if L is None or w.size < MIN_N or not _eligible(w, g, m, v):
        return False
    L.gxk_adam(_ptr(w), _ptr(g), _ptr(m), _ptr(v), lr, b1, b2, eps, wd,
               t, w.size)
    return True


def _i64(a: np.ndarray):
    return a.ctypes.data_as(_i64p)


def bsc_pass_usable(u: np.ndarray, v: np.ndarray) -> bool:
    """Whether :func:`bsc_sample` and :func:`bsc_sweep` take this
    state: the library, a key large enough, two float32 arrays of one
    size that own contiguous, writeable memory below 2**31 elements."""
    return (u.size == v.size < 1 << 31 and _eligible(u, v)
            and u.flags.writeable and v.flags.writeable
            and usable(u.size))


def bsc_sample(u: np.ndarray, v: np.ndarray, momentum: float,
               idx: np.ndarray, vals: np.ndarray,
               pos: np.ndarray) -> np.ndarray:
    """``|v + (momentum * u + g)|`` at ``pos`` (``gxk_bsc_sample``),
    ``g`` the pairs ``(idx, vals)``: what :func:`bsc_sweep` is about to
    leave in ``v`` there. ``u``, ``v`` as :func:`bsc_pass_usable` wants
    them; ``idx`` and ``pos`` int64, contiguous, ascending, inside the
    key (``pos`` distinct); ``vals`` float32, contiguous: the caller's
    to see to."""
    out = np.empty(pos.size, dtype=np.float32)
    lib().gxk_bsc_sample(_ptr(u), _ptr(v), momentum, _i64(idx), _ptr(vals),
                         idx.size, _i64(pos), pos.size, _ptr(out))
    return out


def bsc_sweep(u: np.ndarray, v: np.ndarray, momentum: float,
              idx: np.ndarray, vals: np.ndarray, boundary: float,
              cap: int):
    """The Bi-Sparse pass over one key in one sweep (``gxk_bsc_sweep``):
    ``u *= momentum``, ``vals`` added into ``u`` at ``idx`` in their
    order, ``v += u``, then the first ``cap`` positions, ascending,
    with ``|v| >= boundary`` -> (their values, the positions as int32),
    ``v`` and ``u`` cleared there. Arguments as :func:`bsc_sample`'s."""
    out_idx = np.empty(cap, dtype=np.int32)
    out_val = np.empty(cap, dtype=np.float32)
    got = lib().gxk_bsc_sweep(
        _ptr(u), _ptr(v), u.size, momentum, _i64(idx), _ptr(vals), idx.size,
        boundary, out_idx.ctypes.data_as(_i32p), _ptr(out_val), cap)
    return out_val[:got], out_idx[:got]


def idx_encode(idx: np.ndarray) -> Optional[np.ndarray]:
    """The positions ``idx`` (int32 or int64, contiguous) as the gaps
    between them (``gxk_idx_encode``) -> the coded bytes, padded with
    zeros to a multiple of 4, or None where the positions do not ascend
    strictly from 0 or more."""
    last = int(idx[-1]) if idx.size else 0
    if last < 0:
        return None
    # a gap of g takes at most 1 + g / 128 bytes, and never more than
    # 7 bits a byte of its width allow
    most = idx.size * (5 if idx.dtype.itemsize == 4 else 10)
    out = np.empty(min(idx.size + last // 128, most) + 10, dtype=np.uint8)
    got = lib().gxk_idx_encode(idx.ctypes.data, idx.size,
                               idx.dtype.itemsize == 8,
                               out.ctypes.data_as(_u8p), out.size)
    if got < 0:
        return None
    end = -(-got // 4) * 4
    out[got:end] = 0
    return out[:end]


def idx_decode(buf: np.ndarray, out: np.ndarray, size: int) -> int:
    """``out.size`` positions under ``size`` out of the coded bytes
    ``buf`` (uint8, contiguous, writeable or not) into ``out`` (int32
    or int64, contiguous) -> 0, or ``gxk_idx_decode``'s number for
    what was wrong with the bytes."""
    return lib().gxk_idx_decode(
        buf.ctypes.data_as(_u8p), buf.size, out.size, size,
        out.dtype.itemsize == 8, out.ctypes.data)


def entries_merge_usable(idx_a: np.ndarray, vals_a: np.ndarray,
                         idx_b: np.ndarray, vals_b: np.ndarray) -> bool:
    """Whether :func:`entries_merge` takes these two lists: the library,
    positions of one type (int32 or int64) and float32 values, each
    contiguous and a value a position."""
    return (idx_a.dtype == idx_b.dtype and idx_a.dtype.kind == "i"
            and idx_a.dtype.itemsize in (4, 8)
            and idx_a.flags.c_contiguous and idx_b.flags.c_contiguous
            and idx_a.size == vals_a.size and idx_b.size == vals_b.size
            and _eligible(vals_a, vals_b) and lib() is not None)


def entries_merge(idx_a: np.ndarray, vals_a: np.ndarray,
                  idx_b: np.ndarray, vals_b: np.ndarray):
    """The two sparse lists summed in one pass (``gxk_entries_merge``):
    positions that ascend strictly with their float32 values, twice ->
    (positions, values) of the merged list, views of the first entries
    of two arrays sized for no position in common; a position both hold
    has ``a + b``. None where a list does not ascend strictly.
    Arguments as :func:`entries_merge_usable` wants them."""
    idx = np.empty(idx_a.size + idx_b.size, dtype=idx_a.dtype)
    vals = np.empty(idx.size, dtype=np.float32)
    got = lib().gxk_entries_merge(
        idx_a.ctypes.data, _ptr(vals_a), idx_a.size,
        idx_b.ctypes.data, _ptr(vals_b), idx_b.size,
        idx_a.dtype.itemsize == 8, idx.ctypes.data, _ptr(vals))
    if got < 0:
        return None
    return idx[:got], vals[:got]
