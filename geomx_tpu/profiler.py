"""Tracing/profiling: chrome-trace host events + the round's spans on
a JAX trace's clock.

Plays the role of the reference profiler (reference: src/profiler/
profiler.h:256 Profiler singleton, SetState :270, DumpProfile :304 —
chrome-tracing JSON output; python/mxnet/profiler.py set_config/
set_state/pause/resume/dump surface), re-designed for the TPU stack:

- host-side protocol events (push/pull handling, van traffic, aggregation
  rounds) are recorded by this module into chrome trace-event JSON,
  viewable in chrome://tracing or Perfetto — same artifact the reference
  emits;
- device-side compute profiling is ``jax.profiler``'s (XLA's tracer
  knows the TPU better than any host timer), and a JAX trace is the one
  switch of the ROUND SPANS (:data:`ROUND_SPANS`): :func:`annotate` and
  :func:`scope` open a ``jax.profiler.TraceAnnotation`` whenever JAX is
  loaded in the process and anybody's ``jax.profiler.start_trace``
  session runs: the round's host spans then land on their threads'
  lines of ``/host:CPU``, on the clock of the chip's ``XLA Ops`` lines.
  With no session active a span is one atomic load.

The distributed twist is kept: workers remotely drive SERVER profilers
over the command channel (reference: KVStoreServerProfilerCommand
{kSetConfig,kState,kPause,kDump}, include/mxnet/kvstore.h:49, sent by
kvstore_dist.h:197-203, handled by kvstore_dist_server.h:383-430 which
prefixes dump files with ``rank<N>_``). See
``KVStoreDist.set_profiler_params`` and the server's command handler.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

_lock = threading.Lock()
_events: List[Dict[str, Any]] = []
_state_running = False
_paused = False
_config: Dict[str, Any] = {"filename": "profile.json"}
_t0 = time.monotonic()

# remote profiler command ids (reference: include/mxnet/kvstore.h:49)
CMD_SET_CONFIG = 0
CMD_STATE = 1
CMD_PAUSE = 2
CMD_DUMP = 3


class RoundSpan(NamedTuple):
    name: str       # the span's name in a trace, a constant
    layer: str      # whose time it is when the chip waits
    cls: str        # "work": the thread computes or copies;
    #                 "wait": it sleeps for somebody else's work


# The spans of one training round, from the trainer's ``step()`` down to
# the link, each opened where the work happens (docs/observability.md
# has the file and line of each). Names are constants; node, tier,
# chunk and the round's ``Meta.trace_round`` id ride as arguments. On a
# thread the innermost open span owns the instant, so a parent's time is
# its self time. benchmark/gap_readers.py reads them by these names.
ROUND_SPANS = (
    RoundSpan("trainer.step", "trainer", "work"),
    RoundSpan("trainer.fetch", "trainer", "work"),
    RoundSpan("trainer.pack", "trainer", "work"),
    RoundSpan("trainer.wait", "trainer", "wait"),
    RoundSpan("trainer.unpack", "trainer", "work"),
    RoundSpan("trainer.h2d", "trainer", "work"),
    RoundSpan("trainer.apply", "trainer", "work"),
    RoundSpan("pipeline:send", "van", "work"),
    RoundSpan("pipeline:recv", "van", "work"),
    RoundSpan("van.send", "van", "work"),
    RoundSpan("van.recv", "van", "work"),
    RoundSpan("server.push", "party_server", "work"),
    RoundSpan("server.pull", "party_server", "work"),
    RoundSpan("server.forward", "party_server", "work"),
    RoundSpan("server.pullback", "party_server", "work"),
    RoundSpan("server.select", "select", "work"),
    RoundSpan("server.push.global", "global_server", "work"),
    RoundSpan("server.pull.global", "global_server", "work"),
    RoundSpan("server.respond", "global_server", "work"),
    RoundSpan("link.hold", "link", "wait"),
)


def set_config(**kwargs) -> None:
    """Configure the profiler (reference: profiler.py set_config).

    Recognized key: ``filename`` (chrome-trace output path). Unknown
    keys are stored but ignored, for reference-kwarg compatibility.
    """
    with _lock:
        _config.update(kwargs)


def set_state(state: str = "stop") -> None:
    """'run' starts recording; 'stop' stops (reference: SetState)."""
    global _state_running
    with _lock:
        _state_running = state == "run"


def pause() -> None:
    """Temporarily stop recording without losing state (kPause)."""
    global _paused
    with _lock:
        _paused = True


def resume() -> None:
    global _paused
    with _lock:
        _paused = False


def is_running() -> bool:
    return _state_running and not _paused


def _now_us() -> float:
    return (time.monotonic() - _t0) * 1e6


def now_us() -> float:
    """Current time on the profiler clock (µs since profiler epoch)."""
    return _now_us()


def record(name: str, cat: str, ts_us: float, dur_us: float,
           args: Optional[Dict[str, Any]] = None) -> None:
    """Record one complete ('X') trace event."""
    if not is_running():
        return
    ev = {
        "name": name, "cat": cat, "ph": "X",
        "ts": ts_us, "dur": dur_us,
        "pid": os.getpid(), "tid": threading.get_ident() % (1 << 31),
    }
    if args:
        ev["args"] = args
    with _lock:
        _events.append(ev)


class _NoSpan:
    """What :func:`annotate` gives where nothing would be written."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args) -> None:
        pass


_NO_SPAN = _NoSpan()
_annotation = None      # jax.profiler.TraceAnnotation, once JAX is loaded


def _find_annotation():
    global _annotation
    jax = sys.modules.get("jax")
    # a module half-way through its import has no ``profiler`` yet
    _annotation = getattr(getattr(jax, "profiler", None),
                          "TraceAnnotation", None)
    return _annotation


def annotate(name: str, **args):
    """A span on the JAX trace's clock and nothing else: a
    ``jax.profiler.TraceAnnotation`` named ``name`` (a constant of
    :data:`ROUND_SPANS`; what the span is about rides in ``args`` as
    numbers or constant strings), for ``with``. More arguments can
    follow while it is open (``set_metadata``).

    The one switch is the JAX trace itself: with no session active
    (``TraceAnnotation.is_enabled()``, an atomic load) the span is a
    shared no-op. JAX is never imported from here: a server process
    that did not load it gets the no-op too, and no backend is touched.
    The sites that time themselves for the chrome trace
    (:func:`record` with explicit times: the van) use this beside it;
    everybody else uses :func:`scope`, which is both."""
    ann = _annotation or _find_annotation()
    if ann is None or not ann.is_enabled():
        return _NO_SPAN
    return ann(name, **args)


class _ChromeScope:
    """:func:`scope` while the chrome-trace half is recording."""

    __slots__ = ("_name", "_cat", "_args", "_ann", "_start")

    def __init__(self, name: str, cat: str, args: Dict[str, Any]):
        self._name, self._cat, self._args = name, cat, args
        self._ann = annotate(name, **args)

    def __enter__(self):
        self._start = _now_us()
        self._ann.__enter__()
        return self._ann

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        record(self._name, self._cat, self._start,
               _now_us() - self._start, self._args or None)
        return False


def scope(name: str, cat: str = "geomx", **args):
    """Time a host-side region (the engine-op tag equivalent of the
    reference's PROFILER_MESSAGE_FUNCNAME, kvstore_dist_server.h:570),
    for ``with``. Two halves, each with its own switch:

    - a chrome trace event, while :func:`is_running`;
    - a ``jax.profiler.TraceAnnotation`` (:func:`annotate`), while a
      JAX trace runs — the TPU-idiomatic analogue of the reference's
      VTune ITT domain/task bridge (src/profiler/vtune.cc): host
      protocol spans appear on the XLA trace timeline next to the
      device ops they drive.

    ``with scope(...) as span`` gives the annotation, for
    ``span.set_metadata``."""
    if _state_running and not _paused:
        return _ChromeScope(name, cat, args)
    # annotate(), without a second call on the path of every span
    ann = _annotation or _find_annotation()
    if ann is None or not ann.is_enabled():
        return _NO_SPAN
    return ann(name, **args)


def instant(name: str, cat: str = "geomx", **args: Any) -> None:
    """Record an instant ('i') event — a point-in-time marker for things
    with no duration: snapshot writes, recovery restores, injected
    crashes. Process-scoped so it renders as a full-height line."""
    if not is_running():
        return
    ev = {"name": name, "cat": cat, "ph": "i", "s": "p", "ts": _now_us(),
          "pid": os.getpid(), "tid": threading.get_ident() % (1 << 31)}
    if args:
        ev["args"] = args
    with _lock:
        _events.append(ev)


def counter(name: str, value: float, cat: str = "geomx") -> None:
    """Record an instant counter sample (bytes sent, queue depths...)."""
    if not is_running():
        return
    ev = {"name": name, "cat": cat, "ph": "C", "ts": _now_us(),
          "pid": os.getpid(), "args": {name: value}}
    with _lock:
        _events.append(ev)


def dumps() -> str:
    """Serialize recorded events as chrome trace JSON."""
    with _lock:
        doc = {"traceEvents": list(_events), "displayTimeUnit": "ms"}
    return json.dumps(doc)


def dump(finished: bool = True, filename: Optional[str] = None) -> str:
    """Write the trace file (reference: DumpProfile :304); returns path.

    The write is atomic (tmp + rename): tools/trace_merge.py and the
    chaos-matrix artifact collector read these files from other
    processes, and a dump interrupted by a crash must never leave a
    truncated JSON where a previous good trace stood."""
    path = filename or _config.get("filename", "profile.json")
    data = dumps()
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(data)
    os.replace(tmp, path)
    if finished:
        with _lock:
            _events.clear()
    return path


def reset() -> None:
    global _state_running, _paused
    with _lock:
        _events.clear()
        _state_running = False
        _paused = False
        _config.clear()
        _config["filename"] = "profile.json"


# ----------------------------------------------------------------------
# remote command application (server side)
# ----------------------------------------------------------------------

def apply_remote_command(body: str, rank: int) -> None:
    """Apply a worker-issued profiler command on a server process
    (reference: ProcessServerProfilerCommands, kvstore_dist_server.h:383-
    430). Dump filenames are prefixed ``rank<N>_`` exactly as the
    reference does (:415) so per-server traces don't collide."""
    try:
        d = json.loads(body) if body else {}
    except ValueError:
        return
    cmd = d.get("cmd", -1)
    params = d.get("params", {})
    if cmd == CMD_SET_CONFIG:
        fn = params.get("filename")
        if fn:
            head, tail = os.path.split(fn)
            params["filename"] = os.path.join(head, f"rank{rank}_{tail}")
        set_config(**params)
    elif cmd == CMD_STATE:
        set_state(params.get("state", "stop"))
    elif cmd == CMD_PAUSE:
        if params.get("paused", True):
            pause()
        else:
            resume()
    elif cmd == CMD_DUMP:
        dump(finished=bool(params.get("finished", True)))
