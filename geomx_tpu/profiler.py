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
  With no session active a span is one atomic load;
- while the metrics registry is on (``telemetry.enabled()``) the same
  spans also keep THE ROUND ACCOUNT, profiler or none: each span's self
  time on the wall clock and on its thread's CPU clock, merged once a
  round into the registry's ``round.*`` counters, with what stalls
  every thread at once (``host.gc_ms``, ``host.jax_ms``) beside it;
  where a trainer runs, also by round id, and one record of every
  round that took over twice its worker's median
  (:func:`merge_rounds`).

The distributed twist is kept: workers remotely drive SERVER profilers
over the command channel (reference: KVStoreServerProfilerCommand
{kSetConfig,kState,kPause,kDump}, include/mxnet/kvstore.h:49, sent by
kvstore_dist.h:197-203, handled by kvstore_dist_server.h:383-430 which
prefixes dump files with ``rank<N>_``). See
``KVStoreDist.set_profiler_params`` and the server's command handler.
"""

from __future__ import annotations

import collections
import gc
import json
import logging
import os
import statistics
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

_lock = threading.Lock()
_events: List[Dict[str, Any]] = []
_state_running = False
_paused = False
_accounting = False     # the round account is kept: telemetry.enabled()
# neither the chrome half nor the account: what every span looks at first
_plain = True
_config: Dict[str, Any] = {"filename": "profile.json"}
# the two clocks of a span, by these names so that a test can feed them.
# The chrome trace's epoch is on the first: a span that is both a chrome
# event and a line of the account reads the clock once a side
_wall_ns = time.perf_counter_ns
_cpu_ns = time.thread_time_ns
_t0 = _wall_ns()

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
_SPAN_CLASS = {s.name: s.cls for s in ROUND_SPANS}


def set_config(**kwargs) -> None:
    """Configure the profiler (reference: profiler.py set_config).

    Recognized key: ``filename`` (chrome-trace output path). Unknown
    keys are stored but ignored, for reference-kwarg compatibility.
    """
    with _lock:
        _config.update(kwargs)


def _switch(running: Optional[bool] = None, paused: Optional[bool] = None,
            accounting: Optional[bool] = None) -> None:
    global _state_running, _paused, _accounting, _plain
    with _lock:
        if running is not None:
            _state_running = running
        if paused is not None:
            _paused = paused
        if accounting is not None:
            _accounting = accounting
        _plain = not (_accounting or (_state_running and not _paused))


def set_state(state: str = "stop") -> None:
    """'run' starts recording; 'stop' stops (reference: SetState)."""
    _switch(running=state == "run")


def pause() -> None:
    """Temporarily stop recording without losing state (kPause)."""
    _switch(paused=True)


def resume() -> None:
    _switch(paused=False)


def keep_account(on: bool) -> None:
    """The round account's switch: ``telemetry.enable()`` calls it, so the
    account is kept just while the registry it is merged into is on."""
    _switch(accounting=on)


def is_running() -> bool:
    return _state_running and not _paused


def _now_us() -> float:
    return (_wall_ns() - _t0) / 1e3


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
    """A span on the JAX trace's clock and in the round account, and not
    a chrome event: a ``jax.profiler.TraceAnnotation`` named ``name`` (a
    constant of :data:`ROUND_SPANS`; what the span is about rides in
    ``args`` as numbers or constant strings), for ``with``. More
    arguments can follow while it is open (``set_metadata``).

    The annotation's one switch is the JAX trace itself: with no session
    active (``TraceAnnotation.is_enabled()``, an atomic load) there is
    none. JAX is never imported from here: a server process that did
    not load it opens none either, and no backend is touched. The
    account's switch is the registry's (``telemetry.enabled()``); with
    both off the span is a shared no-op.
    The sites that time themselves for the chrome trace
    (:func:`record` with explicit times: the van) use this beside it;
    everybody else uses :func:`scope`, which is all three."""
    if _accounting and name in _SPAN_CLASS:
        return _Span(name, None, args, True)
    ann = _annotation or _find_annotation()
    if ann is None or not ann.is_enabled():
        return _NO_SPAN
    return ann(name, **args)


class _Span:
    """:func:`scope` / :func:`annotate` while the chrome-trace half
    records or the round account is kept: one read of the wall clock a
    side for both, the thread's CPU clock inside it for the account,
    and the JAX annotation inside those where a trace runs."""

    __slots__ = ("_name", "_cat", "_args", "_ann", "_mine", "_start",
                 "_cpu_start", "_below", "_cpu_below", "_held",
                 "round", "node", "chunk")

    def __init__(self, name: str, cat: Optional[str],
                 args: Dict[str, Any], account: bool):
        self._name, self._cat, self._args = name, cat, args
        ann = _annotation or _find_annotation()
        self._ann = (ann(name, **args)
                     if ann is not None and ann.is_enabled() else None)
        self._mine = _my_account() if account else None

    def __enter__(self):
        global _judging
        mine = self._mine
        self._start = _wall_ns()
        if mine is not None:
            args = self._args
            self.round = args.get("round", -1)
            self.node = args.get("node")
            self.chunk = args.get("chunk", -1)
            self._below = self._cpu_below = 0
            self._held = len(mine.unplaced)
            mine.stack.append(self)
            if not _judging and self._name == "trainer.step":
                # a trainer runs in this process: from here on the
                # threads keep their spans by round id too; a process
                # of servers alone judges no step and keeps none
                _judging = True
            self._cpu_start = _cpu_ns()
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def set_metadata(self, **args) -> None:
        if self._mine is not None:
            self.round = args.get("round", self.round)
            self.node = args.get("node", self.node)
            self.chunk = args.get("chunk", self.chunk)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
        mine = self._mine
        if mine is None:
            wall = _wall_ns() - self._start
        else:
            cpu = _cpu_ns() - self._cpu_start
            wall = _wall_ns() - self._start
            mine.close(self, wall, cpu)
        if self._cat is not None:
            record(self._name, self._cat, (self._start - _t0) / 1e3,
                   wall / 1e3, self._args or None)
        return False


def scope(name: str, cat: str = "geomx", **args):
    """Time a host-side region (the engine-op tag equivalent of the
    reference's PROFILER_MESSAGE_FUNCNAME, kvstore_dist_server.h:570),
    for ``with``. Three parts, each with its own switch:

    - a chrome trace event, while :func:`is_running`;
    - a ``jax.profiler.TraceAnnotation`` (:func:`annotate`), while a
      JAX trace runs — the TPU-idiomatic analogue of the reference's
      VTune ITT domain/task bridge (src/profiler/vtune.cc): host
      protocol spans appear on the XLA trace timeline next to the
      device ops they drive;
    - a line of the round account, while ``telemetry.enabled()`` and
      ``name`` is one of :data:`ROUND_SPANS`.

    ``with scope(...) as span`` gives what takes ``span.set_metadata``."""
    if not _plain:
        chrome = _state_running and not _paused
        account = _accounting and name in _SPAN_CLASS
        if chrome or account:
            return _Span(name, cat if chrome else None, args, account)
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
    _switch(running=False, paused=False)
    with _lock:
        _events.clear()
        _config.clear()
        _config["filename"] = "profile.json"
    reset_rounds()


# ----------------------------------------------------------------------
# the round account
# ----------------------------------------------------------------------
# While ``telemetry.enabled()`` every span of ROUND_SPANS books its SELF
# time (the innermost open span of a thread owns the instant, as in
# benchmark/gap_readers.py::self_segments) on both clocks into numbers
# its own thread holds and nobody else writes: no lock, no registry
# call, and nothing allocated a span that outlives the close (the
# collector's phase stays the program's). When a worker's
# ``trainer.step`` closes, and at every ``telemetry.snapshot()``, one
# thread reads what the others have added since it last looked and adds
# it to the registry's counters under one hold of its lock:
#
#   round.work_ms{span=}, round.work_cpu_ms{span=}   class ``work``
#   round.wait_ms{span=}                             class ``wait``
#   round.spans{span=}                               how many closed
#   host.gc_ms{gen=}, host.gc_collections{gen=}      gc.callbacks
#   host.jax_ms{what=trace|lower|compile|cache_load} jax.monitoring
#
# In a process that runs a trainer the threads also keep those numbers
# by round id, until the round's step has been held against its worker's
# other steps: one that took over SLOW_FACTOR times their median leaves
# one record, which names the span, the thread and the clocks
# (docs/observability.md has an example).

SLOW_FACTOR = 2         # a slow step: over this many medians ...
SLOW_MEDIAN_OF = 32     # ... of the worker's last steps, this many,
SLOW_SEEN = 8           # once it has this many: the first ones wait for
#                         the eighth and are held to that median
SLOW_KEPT = 8           # records a process keeps: the slowest

_JAX_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}

_rounds_log = logging.getLogger("geomx_tpu.rounds")
_merge_lock = threading.Lock()
_local = threading.local()
_accounts: List["_ThreadAccount"] = []
# (round, "gc" | "jax", generation | kind, ns, thread name): what stalls
# every thread at once, booked by the thread it ran on
_host: List[tuple] = []
_gc_open = None         # (start ns, annotation) of the running collection
_jax_watched = False
_judging = False        # a trainer.step has opened: rounds are kept by id
_newest_round = -1      # the newest id a span has closed with
_keep_from = -1         # ids below it have been judged: a thread lets go
# below: the merging thread's, under _merge_lock
_host_rounds: Dict[int, dict] = {}  # round id -> {"gc": .., "jax": ..}
_steps: List[tuple] = []        # closed trainer.steps not yet judged
_step_ns: Dict[str, Any] = {}   # worker node -> its last steps' lengths
_early: Dict[str, list] = {}    # worker node -> its steps before the
#                                 eighth, each with its round's account
_slow: List[Dict[str, Any]] = []


class _ThreadAccount:
    """One thread's share of the account. Only its thread writes it,
    and only by adding to numbers in place; the merging thread reads,
    and keeps in ``booked`` what it has handed to the registry."""

    __slots__ = ("thread", "stack", "unplaced", "totals", "booked",
                 "rounds")

    def __init__(self):
        self.thread = threading.current_thread()
        self.stack: List[_Span] = []    # the open spans, innermost last
        # span -> [closed, self wall ns, self cpu ns], since the start
        self.totals: Dict[str, list] = {}
        self.booked: Dict[str, list] = {}
        # while ``_judging``: round id -> {(node, span): [closed, self
        # wall ns, self cpu ns, (the longest instance's wall ns, cpu
        # ns, chunk)]}, ids from ``_keep_from`` on
        self.rounds: Dict[int, dict] = {}
        # (node, span, self wall ns, self cpu ns, chunk) of spans that
        # closed with no round id of their own, until an enclosing span
        # closes with one
        self.unplaced: List[tuple] = []

    def close(self, span: "_Span", wall: int, cpu: int) -> None:
        stack = self.stack
        stack.pop()
        name = span._name
        own, own_cpu = wall - span._below, cpu - span._cpu_below
        if stack:
            up = stack[-1]
            up._below += wall
            up._cpu_below += cpu
        t = self.totals.get(name)
        if t is None:
            t = self.totals[name] = [0, 0, 0]
        t[0] += 1
        t[1] += own
        t[2] += own_cpu
        if not _judging:
            return
        rid = span.round
        step = name == "trainer.step"
        if step and rid < 0:
            rid = _newest_round + 1     # a local store allots no id
        held = self.unplaced
        if rid < 0:
            if stack:
                held.append((span.node, name, own, own_cpu, span.chunk))
            else:
                del held[span._held:]
            return
        by = self.rounds.get(rid)
        if by is None:
            by = self._open_round(rid)
        if len(held) > span._held:
            for node, inner, w, c, chunk in held[span._held:]:
                _book(by, (node or span.node, inner), 1, w, c,
                      (w, c, chunk))
            del held[span._held:]
        _book(by, (span.node, name), 1, own, own_cpu,
              (own, own_cpu, span.chunk))
        if step:
            _merge(rid, step=(rid, span.node or "-", span._start, wall))

    def _open_round(self, rid: int) -> dict:
        global _newest_round
        rounds = self.rounds
        for old in [r for r in rounds if r < _keep_from]:
            del rounds[old]
        if rid > _newest_round:
            _newest_round = rid
        by = rounds[rid] = {}
        return by


def _my_account() -> _ThreadAccount:
    try:
        return _local.mine
    except AttributeError:
        mine = _local.mine = _ThreadAccount()
        with _lock:
            _accounts.append(mine)
            _watch_host()
        return mine


def _round_in_hand() -> int:
    """The round of the calling thread's innermost span that has one,
    else the newest any thread has closed a span of."""
    mine = getattr(_local, "mine", None)
    if mine is not None:
        for span in reversed(mine.stack):
            if span.round >= 0:
                return span.round
    return _newest_round


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """``gc.callbacks``: one collection, timed; on a JAX trace also the
    annotation ``host.gc``, which is no round span: no ``gap.*`` metric
    reads it."""
    global _gc_open
    if phase == "start":
        if not _accounting:
            return
        ann = _annotation
        if ann is not None and ann.is_enabled():
            ann = ann("host.gc", gen=info["generation"])
            ann.__enter__()
        else:
            ann = None
        _gc_open = (_wall_ns(), ann)
    elif _gc_open is not None:
        start, ann = _gc_open
        _gc_open = None
        ns = _wall_ns() - start
        if ann is not None:
            ann.__exit__(None, None, None)
        _host.append((_round_in_hand(), "gc", info["generation"], ns,
                      threading.current_thread().name))


def _on_jax(event: str, secs: float, **_kw) -> None:
    """``jax.monitoring``: a jit that traces, lowers, compiles or loads
    from the compile cache."""
    what = _JAX_DURATIONS.get(event)
    if what is not None and _accounting:
        _host.append((_round_in_hand(), "jax", what, int(secs * 1e9),
                      threading.current_thread().name))


def _watch_host() -> None:
    """Register the two callbacks, once; jax.monitoring's only where JAX
    is loaded already (as :func:`_find_annotation`: never imported from
    here). Under ``_lock``."""
    global _jax_watched
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    if not _jax_watched:
        monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
        if monitoring is not None:
            monitoring.register_event_duration_secs_listener(_on_jax)
            _jax_watched = True


def _book(spans: dict, key, n: int, wall: int, cpu: int,
          longest: tuple) -> None:
    """Add to ``{key: [closed, wall ns, cpu ns, longest]}``; ``longest``
    is the one longest instance: ``(wall ns, cpu ns, chunk)``, and in a
    record its thread's name behind them."""
    e = spans.get(key)
    if e is None:
        spans[key] = [n, wall, cpu, longest]
        return
    e[0] += n
    e[1] += wall
    e[2] += cpu
    if longest[0] > e[3][0]:
        e[3] = longest


def _gathered(rid: int, until: Optional[int]) -> tuple:
    """What every thread holds of the rounds ``rid`` up to ``until`` (a
    step in chunks has an id a chunk, up to the next step's first):
    ``({node: spans}, {"gc": .., "jax": ..})`` in :func:`_book`'s form.
    A dict another thread adds to is copied in one bytecode."""
    def within(r):
        return r >= rid and (until is None or r < until)

    nodes: Dict[str, dict] = {}
    host: Dict[str, dict] = {"gc": {}, "jax": {}}
    for mine in list(_accounts):
        who = mine.thread.name
        for r in list(mine.rounds):
            by = mine.rounds.get(r) if within(r) else None
            for (node, name), e in list(by.items()) if by else ():
                n, wall, cpu, longest = e
                _book(nodes.setdefault(node or "-", {}), name, n, wall,
                      cpu, longest + (who,))
    for r, acct in _host_rounds.items():
        if within(r):
            for kind, labels in acct.items():
                for label, e in labels.items():
                    _book(host[kind], label, *e)
    return nodes, host


def _merge(upto: Optional[int], step: Optional[tuple] = None) -> None:
    """Bring the registry's counters up to what every thread has closed,
    under one hold of its lock; then hold the steps of the rounds older
    than ``upto``, the one in hand (``None``: every step that has
    closed, as ``telemetry.snapshot()`` asks), against their workers'
    medians, and let their rounds go."""
    global _keep_from
    tel = sys.modules["geomx_tpu.telemetry"]    # it imports this module
    found = []
    with _merge_lock:
        if step is not None:
            _steps.append(step)
        totals: Dict[tuple, list] = {}  # counter labels -> [n, wall, cpu]
        for mine in list(_accounts):
            # asked first: a thread may close its last span and end
            # between the two looks
            gone = not mine.thread.is_alive()
            moved = False
            for name, now in list(mine.totals.items()):
                was = mine.booked.get(name)
                if was is None:
                    was = mine.booked[name] = [0, 0, 0]
                now = list(now)
                if now != was:
                    moved = True
                    t = totals.setdefault(("span", name), [0, 0, 0])
                    for i in range(3):
                        t[i] += now[i] - was[i]
                    was[:] = now
            if gone and not moved and all(
                    r < _keep_from for r in list(mine.rounds)):
                with _lock:
                    _accounts.remove(mine)
        for rid, kind, label, ns, who in _taken(_host):
            t = totals.setdefault(
                ("gen" if kind == "gc" else "what", label), [0, 0, 0])
            t[0] += 1
            t[1] += ns
            if _judging and rid >= 0:
                acct = _host_rounds.setdefault(rid, {"gc": {}, "jax": {}})
                _book(acct[kind], label, 1, ns, 0, (ns, 0, -1, who))
        rows = []
        for labels, (n, wall, cpu) in totals.items():
            kind, labels = labels[0], (labels,)
            if kind == "gen":
                rows.append(("host.gc_ms", labels, wall / 1e6))
                rows.append(("host.gc_collections", labels, n))
            elif kind == "what":
                rows.append(("host.jax_ms", labels, wall / 1e6))
            else:
                rows.append(("round.spans", labels, n))
                if _SPAN_CLASS[labels[0][1]] == "work":
                    rows.append(("round.work_ms", labels, wall / 1e6))
                    rows.append(("round.work_cpu_ms", labels, cpu / 1e6))
                else:
                    rows.append(("round.wait_ms", labels, wall / 1e6))
        if rows:
            tel.counters_add(rows)
        ids = sorted({s[0] for s in _steps})
        later = []
        for st in _steps:
            rid, node = st[0], st[1]
            if upto is not None and rid >= upto:
                later.append(st)
                continue
            until = min((i for i in ids if i > rid), default=None)
            past = _step_ns.setdefault(
                node, collections.deque(maxlen=SLOW_MEDIAN_OF))
            past.append(st[3])
            judged = [(st, None)]
            if len(past) <= SLOW_SEEN:
                # the first steps wait for the eighth, with what their
                # rounds held: the threads let go of it below
                _early.setdefault(node, []).append(
                    (st, _gathered(rid, until)))
                if len(past) < SLOW_SEEN:
                    continue
                judged = _early.pop(node)
            median = statistics.median(past)
            for one, acct in judged:
                if one[3] > SLOW_FACTOR * median:
                    found.append(_record(one, median, *(
                        acct or _gathered(rid, until))))
        _steps[:] = later
        if ids:
            _keep_from = (min(s[0] for s in later) if later
                          else _newest_round + 1)
        for rid in [r for r in _host_rounds if r < _keep_from]:
            del _host_rounds[rid]
        if found:
            _slow.extend(found)
            _slow.sort(key=lambda r: -r["ms"])
            del _slow[SLOW_KEPT:]
    for rec in found:
        _rounds_log.warning("slow round %s", json.dumps(
            rec, separators=(",", ":"), default=str))
        tel.event("round.slow", cat="profiler", round=rec["round"],
                  node=rec["node"], ms=rec["ms"])


def _taken(lines: list) -> list:
    """The front of a list other threads append to, removed."""
    got = lines[:len(lines)]
    del lines[:len(got)]
    return got


def _record(step: tuple, median: float, nodes: Dict[str, dict],
            host: Dict[str, dict]) -> Dict[str, Any]:
    """The record of one slow round."""
    rid, node, start, ns = step

    def ms(x):
        return round(x / 1e6, 3)

    def said(spans):
        return {name: {"n": n, "ms": ms(wall), "cpu_ms": ms(cpu),
                       "longest": {"ms": ms(lw), "cpu_ms": ms(lc),
                                   "thread": who, "chunk": chunk}}
                for name, (n, wall, cpu, (lw, lc, chunk, who))
                in sorted(spans.items())}

    return {"round": rid, "node": node, "start_ns": start, "ms": ms(ns),
            "median_ms": ms(median),
            "spans": said(nodes.get(node, {})),
            "gc_ms": {str(g): ms(e[1])
                      for g, e in sorted(host["gc"].items())},
            "gc_threads": sorted({e[3][3] for e in host["gc"].values()}),
            "jax_ms": {w: ms(e[1]) for w, e in sorted(host["jax"].items())},
            "others": {who: said(spans)
                       for who, spans in sorted(nodes.items())
                       if who != node}}


def merge_rounds() -> List[Dict[str, Any]]:
    """Bring the registry's ``round.*`` and ``host.*`` counters up to
    what has closed so far, judge every step that has, and give the
    records of the process's slow rounds, the slowest first
    (``telemetry.snapshot()["slow_rounds"]``)."""
    if _accounts or _host:
        _merge(None)
    with _merge_lock:
        return list(_slow)


def reset_rounds() -> None:
    """Forget the account: a thread's next span starts a new one."""
    global _local, _gc_open, _jax_watched
    global _judging, _newest_round, _keep_from
    with _merge_lock, _lock:
        _local = threading.local()
        del _accounts[:], _host[:], _steps[:], _slow[:]
        _host_rounds.clear()
        _step_ns.clear()
        _early.clear()
        _gc_open = None
        _judging, _newest_round, _keep_from = False, -1, -1
        if _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
        if _jax_watched:
            sys.modules["jax"].monitoring.unregister_event_duration_listener(
                _on_jax)
            _jax_watched = False


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
