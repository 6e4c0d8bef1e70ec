"""Where the program meets the installed interpreter and jax: which
device it runs on, where compiled programs are cached, how compilations
are counted, and what the collector need not walk again.

Every entry point that measures or proves something on the accelerator
(``benchmark/run.py``, ``chip_smoke.py``, ``tools/attention_bench.py``)
goes through :func:`require_tpu`; none of them sets ``jax_platforms`` or
falls back to the CPU. Tests and the CPU launch scripts pick the CPU
from OUTSIDE (``JAX_PLATFORMS=cpu``), never in here.

JAX is imported lazily, like everywhere else in the package: infra
roles must not pay for it.
"""

from __future__ import annotations

import gc
import os
from typing import Dict, List

from geomx_tpu import telemetry
from geomx_tpu.config import env_str

__all__ = ["REPO_ROOT", "setup_compile_cache", "device_stamp",
           "require_tpu", "CompileCounter", "settle_heap"]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def setup_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, so nothing
    is set in code. Otherwise ``<checkout>/.jax_cache`` — a fixed path,
    because the path is part of the cache key and a directory that moves
    (temp name, pid, time) never hits."""
    env = env_str("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_stamp() -> Dict[str, object]:
    """The device as jax reports it — stamped on every result."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> Dict[str, object]:
    """:func:`device_stamp`, or ``RuntimeError`` when jax's default
    backend is not a TPU. There is no CPU fallback behind this."""
    stamp = device_stamp()
    if stamp["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: jax's default backend is {stamp['platform']!r} "
            f"({stamp['kind']}, {stamp['count']} device(s)); this entry "
            "point runs on the accelerator only and has no CPU fallback")
    return stamp


def settle_heap() -> int:
    """One full collection, then everything that survived it moves out of
    the collector's sight (``gc.freeze()``); returns how many objects
    the process holds frozen.

    For the moment a process has built what it keeps for the life of the
    job (compiled programs and their jaxprs, the model's pytrees, jax's
    and flax's modules, the topology): a generation-2 collection walks
    every container the process tracks, none of that is ever garbage,
    and the pass holds the interpreter lock, so every thread waits it
    out. Afterwards a full pass walks what was allocated since. The
    collection comes first so that no garbage is frozen; the collector
    stays on, with its thresholds. A later call freezes what has been
    built since. A frozen object that later joins a dead cycle is never
    freed: call this where what is alive stays alive."""
    gc.collect()
    gc.freeze()
    frozen = gc.get_freeze_count()
    telemetry.counter_inc("host.gc_freezes")
    telemetry.gauge_set("host.gc_frozen_objects", frozen)
    return frozen


class CompileCounter:
    """Counts executables jax builds or loads from the persistent cache
    (``jax.monitoring`` events), so a run can assert that nothing
    compiled after its warm-up. Listeners cannot be unregistered, so
    make one per process."""

    _BUILD = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        from jax import monitoring

        # append-only (atomic under the GIL): worker threads compile too
        self._builds: List[float] = []   # seconds, one per program
        self._hits: List[str] = []
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    @property
    def programs(self) -> int:
        """Executables built OR loaded from the persistent cache."""
        return len(self._builds)

    @property
    def seconds(self) -> float:
        return sum(self._builds)

    @property
    def cache_hits(self) -> int:
        return len(self._hits)

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if name == self._BUILD:
            self._builds.append(secs)

    def _event(self, name: str, **_kw) -> None:
        if name == self._HIT:
            self._hits.append(name)
