"""Build-on-demand for the C++ helpers under ``native/``.

A library is named by a hash of the source it was built from
(``native/libgeomx_<name>.<sha12>.so``), so a binary that does not match
the ``.cc`` in this checkout is never loaded — file times do not survive
a copy of the tree, a content hash does. Stdlib only: safe to import
from van/handler threads.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
from typing import Sequence

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")


def ensure_built(name: str, flags: Sequence[str]) -> str:
    """Path of the shared library for ``native/<name>.cc`` compiled with
    ``flags``, building it with g++ when this exact source+flags pair
    has not been built here yet. Raises OSError / SubprocessError when
    it cannot be built; callers decide what to fall back to."""
    src = os.path.join(NATIVE_DIR, f"{name}.cc")
    cmd = ["g++", *flags, "-std=c++17", "-fPIC", "-Wall", "-shared"]
    with open(src, "rb") as f:
        digest = hashlib.sha256(
            f.read() + " ".join(cmd).encode()).hexdigest()[:12]
    lib = os.path.join(NATIVE_DIR, f"libgeomx_{name}.{digest}.so")
    if os.path.exists(lib):
        return lib
    # build to a process-unique temp path, then atomically rename:
    # several processes (scheduler/servers/workers on one host) may race
    # through a fresh checkout's first build, and interleaved writes to
    # one output path would leave a permanently corrupt .so
    tmp = f"{lib}.{os.getpid()}.tmp"
    try:
        subprocess.run([*cmd, "-o", tmp, src], check=True,
                       capture_output=True, timeout=120)
        os.replace(tmp, lib)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return lib
