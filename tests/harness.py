"""Shared scaffolding for the topology tests (not a test module).

Everything a test needs to stand a cluster up on threads, kill and
revive its members, and take it down again lives here, so that no test
module imports another. ``SingleTier`` is the classic one-tier PS
(scheduler + servers + workers, the reference's global-tier recovery is
unimplemented: van.cc:224 TODO); ``Topology`` is the product's two-tier
``InProcessHiPS`` with the suite's defaults.
"""

import os
import re
import signal
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

from geomx_tpu.compression import BSCCompressor
from geomx_tpu.compression.entries import decode_positions
from geomx_tpu.config import Config
from geomx_tpu.kvstore.dist import KVStoreDist
from geomx_tpu.kvstore.server import KVStoreDistServer
from geomx_tpu.ps import base as psbase
from geomx_tpu.ps.kv_app import KVPairs, ReqMeta
from geomx_tpu.ps.message import Role
from geomx_tpu.ps.postoffice import Postoffice
from geomx_tpu.simulate import InProcessHiPS, free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every wait of the harness, in seconds. Sized for a test, not for a
# cluster: a healthy step of these topologies takes well under a second,
# so a test that does fail costs tens of seconds, not the product's
# 300 s / 600 s defaults.
DEADLINES = {
    "op_timeout_s": 20.0,        # Config: push / pull / wait give up
    "barrier_timeout_s": 20.0,   # Config: start-up and exit barriers
    "start_s": 20.0,             # a tier, or a revived node, comes up
    "detect_s": 10.0,            # heartbeat lapse seen / declared
    "join_s": 20.0,              # _parallel's threads; a tier's exit
    "lifetime_s": 120.0,         # scheduler's passive exit wait: no tier
                                 # outlives conftest's per-test limit
}

# fast failure detection: a lapse is seen ~1 s after the kill
HB = {"heartbeat_interval_s": 0.2, "heartbeat_timeout_s": 1.0}


class Topology(InProcessHiPS):
    """The product in-process topology (geomx_tpu.simulate.InProcessHiPS)
    with test-suite defaults: 2 workers per party, like the reference's
    12-process demo (scripts/cpu/run_vanilla_hips.sh)."""

    def __init__(self, num_parties=2, workers_per_party=2, **kw):
        super().__init__(num_parties=num_parties,
                         workers_per_party=workers_per_party, **kw)


class _Background:
    """One call on a helper thread, so the test can watch a blocked round
    from outside; ``result()`` hands back what it returned or raised."""

    def __init__(self, fn):
        self._out = None
        self.error = None

        def run():
            try:
                self._out = fn()
            except BaseException as e:  # noqa: BLE001 — raised by result()
                self.error = e

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def done(self):
        return not self._thread.is_alive()

    def join(self, timeout):
        self._thread.join(timeout)

    def result(self, timeout=None):
        timeout = DEADLINES["join_s"] if timeout is None else timeout
        self.join(timeout)
        if not self.done():
            raise TimeoutError(f"background call still running after "
                               f"{timeout}s")
        if self.error is not None:
            raise self.error
        return self._out


def _parallel(fns, timeout=None):
    """Run ``fns`` concurrently (each node acts independently) and return
    their results in order; re-raise the first error, and raise if a
    call is still running at the one deadline they share."""
    timeout = DEADLINES["join_s"] if timeout is None else timeout
    calls = [_Background(fn) for fn in fns]
    deadline = time.monotonic() + timeout
    for c in calls:
        c.join(max(deadline - time.monotonic(), 0.0))
    for c in calls:
        if c.error is not None:
            raise c.error
    return [c.result(0.0) for c in calls]


def _poll(cond, what, timeout=None, period=0.02):
    """Wait until ``cond()`` is true; fail naming ``what`` at the deadline."""
    timeout = DEADLINES["detect_s"] if timeout is None else timeout
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() >= deadline:
            raise AssertionError(f"timed out after {timeout}s waiting for "
                                 f"{what}")
        time.sleep(period)


def _round(kv, key, w0, expect):
    kv.push(key, np.ones_like(w0))
    out = np.zeros_like(w0)
    kv.pull(key, out=out)
    kv.wait()
    np.testing.assert_allclose(out, expect)


def _kill(kv):
    """Hard worker death: the van stops with no goodbye and no barrier
    (close() on a stopped van skips the exit protocol)."""
    kv.po.van.stop()


def _wait_dead(topo, dead_id, timeout=None):
    """The scheduler's heartbeat scan sees ``dead_id`` lapsed."""
    _poll(lambda: dead_id in topo.sched_po.van.dead_nodes(),
          f"node {dead_id}'s heartbeat lapse at the scheduler", timeout)


def _wait_declared(vans, dead_id, timeout=None):
    """Every van in ``vans`` has received the DEAD_NODE declaration."""
    _poll(lambda: all(dead_id in v.declared_dead_ids() for v in vans),
          f"nodes {[v.my_id for v in vans]} to learn that {dead_id} is dead",
          timeout)


class SingleTier:
    """scheduler + N servers + M workers with fast heartbeats, as a
    context manager that owns every node it made, the revived ones too.

    ``extra`` merges into every node's Config (snapshot dirs, fault
    plans, resend knobs...) so robustness tests configure the whole tier
    the way a launch script would via environment variables. On exit the
    workers close together (rank 0 stops the servers, everyone meets in
    the exit barrier); whatever is still up after a bounded wait has its
    van stopped, and the test fails if a node raised, if the exit
    protocol did not finish by itself, or if a thread of the tier lives.
    """

    def __init__(self, extra=None, num_servers=1, num_workers=2):
        self.port = free_port()
        self.extra = dict(extra or {})
        self.num_servers = num_servers
        self.num_workers = num_workers
        self.threads = []
        self.errors = []
        self.sched_po = None
        self.server = None
        self.servers = []     # the live set: a revived server replaces
        self.workers = []     # the one whose id it took; workers append
        self._all_servers = []

    def __enter__(self):
        try:
            return self.start()
        except BaseException:
            self._stop_leftovers()
            raise

    def __exit__(self, exc_type, exc, tb):
        try:
            self.stop()
        except BaseException as e:  # noqa: BLE001 — see below
            if exc_type is None:
                raise
            # the body's failure is the finding; teardown's is its echo
            print(f"SingleTier teardown after a failed test: {e!r}",
                  file=sys.stderr)
        return False

    def _run(self, fn, name):
        def w():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — raised by stop()
                self.errors.append(e)

        t = threading.Thread(target=w, name=name, daemon=True)
        t.start()
        self.threads.append(t)
        return t

    def _cfg(self, **kw):
        base = dict(ps_root_uri="127.0.0.1", ps_root_port=self.port,
                    num_workers=self.num_workers,
                    num_servers=self.num_servers,
                    op_timeout_s=DEADLINES["op_timeout_s"],
                    barrier_timeout_s=DEADLINES["barrier_timeout_s"], **HB)
        base.update(self.extra)
        base.update(kw)
        return Config(**base)

    def start(self):
        sched_cfg = dict(HB)
        sched_cfg.update(self.extra)
        self.sched_po = Postoffice(
            my_role=Role.SCHEDULER, is_global=False,
            root_uri="127.0.0.1", root_port=self.port,
            num_workers=self.num_workers, num_servers=self.num_servers,
            cfg=Config(**sched_cfg))

        def sched():
            self.sched_po.start(DEADLINES["start_s"])
            self.sched_po.barrier(psbase.ALL_GROUP,
                                  timeout=DEADLINES["start_s"])
            self.sched_po.barrier(psbase.ALL_GROUP,
                                  timeout=DEADLINES["lifetime_s"])
            self.sched_po.van.stop()

        self._run(sched, "tier-scheduler")
        for _ in range(self.num_servers):
            self._start_server()
        self.server = self.servers[0]
        boxes = [[] for _ in range(self.num_workers)]
        for i, box in enumerate(boxes):
            self._run(lambda b=box: b.append(
                KVStoreDist(cfg=self._cfg(role="worker"))), f"tier-worker{i}")
        try:
            _poll(lambda: self.errors or all(boxes), "the tier's workers",
                  DEADLINES["start_s"])
        finally:
            self.workers = [b[0] for b in boxes if b]
        if self.errors:
            raise self.errors[0]
        return self

    def _start_server(self, **cfg_kw):
        srv = KVStoreDistServer(self._cfg(role="server", **cfg_kw))
        self._all_servers.append(srv)
        self.servers.append(srv)
        self._run(srv.run, f"tier-server{len(self._all_servers) - 1}")
        return srv

    # -- faults and recovery ----------------------------------------------

    def revive_worker(self, **cfg_kw):
        """A fresh worker registers with the scheduler; where a worker's
        heartbeat has lapsed it is handed that slot (``is_recovery``)."""
        kv = KVStoreDist(cfg=self._cfg(role="worker", **cfg_kw))
        self.workers.append(kv)
        return kv

    def revive_server(self, **cfg_kw):
        """A fresh server registers, restores what the configuration lets
        it restore, and serves; it replaces the server whose id it took."""
        srv = self._start_server(**cfg_kw)
        _poll(lambda: self.errors or srv._ready.is_set(),
              "the revived server to become ready", DEADLINES["start_s"])
        if self.errors:
            raise self.errors[0]
        self.servers = [s for s in self.servers
                        if s is srv or s.po_local.my_id != srv.po_local.my_id]
        self.server = self.servers[0]
        return srv

    # -- teardown ----------------------------------------------------------

    def _vans(self):
        pos = [self.sched_po] + [kv.po for kv in self.workers]
        pos += [s.po_local for s in self._all_servers]
        return [po.van for po in pos if po is not None]

    def stop(self):
        """Exit protocol, bounded; raises what went wrong in the tier."""
        problems = []
        try:
            _parallel([kv.close for kv in self.workers])
        except BaseException as e:  # noqa: BLE001 — reported with the rest
            problems.append(e)
        deadline = time.monotonic() + DEADLINES["join_s"]
        for t in self.threads:
            t.join(max(deadline - time.monotonic(), 0.0))
        late = [t.name for t in self.threads if t.is_alive()]
        if late:
            problems.append(AssertionError(
                f"tier did not shut down by itself: {late} still running "
                f"{DEADLINES['join_s']}s after the workers closed"))
        alive = self._stop_leftovers()
        if alive:
            problems.append(AssertionError(
                f"threads of the tier outlive it: {alive}"))
        problems = self.errors + problems
        if problems:
            raise problems[0]

    def _stop_leftovers(self):
        """Stop whatever is still up, so that nothing of this tier lives
        on into the next test; names the threads that will not end."""
        for srv in self._all_servers:
            srv._stop.set()
        vans = self._vans()
        for van in vans:
            if not van.stopped.is_set():
                van.stop()
        deadline = time.monotonic() + DEADLINES["join_s"]
        threads = self.threads + [t for v in vans for t in v._threads]
        for t in threads:
            t.join(max(deadline - time.monotonic(), 0.0))
        return [t.name for t in threads if t.is_alive()]


def count_sent_payload(monkeypatch):
    """Every data message any van of this process writes from here on
    adds its parts' bytes to the returned list (one entry a write)."""
    from geomx_tpu.ps.van import Van
    sent = []
    inner = Van._send_one_inner

    def counting(self, target, msg):
        if not msg.is_control:
            sent.append(msg.payload_bytes())
        return inner(self, target, msg)

    monkeypatch.setattr(Van, "_send_one_inner", counting)
    return sent


# -- a bare tier of Postoffices (no kvstore on top) -----------------------


def make_tier(num_workers=2, num_servers=1, is_global=False, cfg=None):
    """Boot a full tier in-process; returns (scheduler, servers, workers)."""
    kw = dict(is_global=is_global, root_uri="127.0.0.1",
              root_port=free_port(), num_workers=num_workers,
              num_servers=num_servers, cfg=cfg)
    sched = Postoffice(my_role=Role.SCHEDULER, **kw)
    servers = [Postoffice(my_role=Role.SERVER, **kw)
               for _ in range(num_servers)]
    workers = [Postoffice(my_role=Role.WORKER, **kw)
               for _ in range(num_workers)]
    pos = [sched] + servers + workers
    _parallel([lambda po=po: po.start(DEADLINES["start_s"]) for po in pos])
    for po in pos:
        assert po.van.ready.is_set(), "rendezvous failed"
    return sched, servers, workers


def shutdown(*pos):
    for po in pos:
        po.finalize(do_barrier=False)


# -- the real multi-process launch -----------------------------------------


def _run_launch(script: str, extra_args, n_iters: int, timeout: float,
                expect_lines: int = 0, env_extra=None,
                pattern: str = r"Test Acc (\d+\.\d+)",
                pass_max_iters: bool = True):
    env = dict(os.environ)
    if env_extra:
        env.update(env_extra)
    env.update({
        "GPORT": str(free_port()), "CPORT": str(free_port()),
        "APORT": str(free_port()), "BPORT": str(free_port()),
        "JAX_PLATFORMS": "cpu",
        "PYTHON": sys.executable,
        # don't inherit the conftest's 8-device virtual mesh into 12
        # separate processes
        "XLA_FLAGS": "",
    })
    argv = ["bash", os.path.join(REPO, "scripts", script)]
    if pass_max_iters:
        argv += ["--max-iters", str(n_iters)]
    proc = subprocess.Popen(
        [*argv, *extra_args],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        pytest.fail(f"launch timed out; output:\n{out[-4000:]}")

    assert proc.returncode == 0, f"launch failed:\n{out[-4000:]}"
    accs = [float(m) for m in re.findall(pattern, out)]
    expect = expect_lines or n_iters
    assert len(accs) == expect, \
        f"expected {expect} iteration lines, got:\n{out[-4000:]}"

    # clean exits: every background process of the group must terminate
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            os.killpg(proc.pid, 0)
        except ProcessLookupError:
            break  # whole group gone
        time.sleep(0.5)
    else:
        os.killpg(proc.pid, signal.SIGKILL)
        pytest.fail("background topology processes did not exit cleanly")
    return accs


# ---------------------------------------------------------------------------
# servers without sockets (as tests/test_server_protocol.py builds them)
# ---------------------------------------------------------------------------

KEY = 5


class RecordingApp:
    def __init__(self):
        self.responses = []

    def response(self, req, kvs=None, body=""):
        self.responses.append((req, kvs))


def push_req(sender, ts, compr, head=0, pull=True, num_merge=1,
             trace_round=-1, global_tier=False):
    return ReqMeta(sender=sender, timestamp=ts, customer_id=0, push=True,
                   pull=pull, simple_app=False, head=head, body="",
                   priority=0, version=0, iters=0, compr=compr,
                   num_merge=num_merge, trace_round=trace_round,
                   global_tier=global_tier)


def server_without_sockets(parties, is_global, fsa_slice_elems=0):
    s = KVStoreDistServer.__new__(KVStoreDistServer)
    s._lock = threading.RLock()
    s._states, s._key_total = {}, {}
    s._party_nsrv, s._party_nsrv_by_sender = 1, {}
    s._fsa_slice_elems = fsa_slice_elems
    s.is_global_server = is_global
    s._tier = "global" if is_global else "local"
    s.sync_global_mode = True
    s.updater = s.ts_global = s.ts_local = None
    s.use_hfa = False
    s.gc = BSCCompressor(0.01)
    s.cfg = types.SimpleNamespace(bigarray_bound=1 << 40, num_parties=0,
                                  enable_central_worker=False)
    # the vans name a server's round spans, no more
    van = types.SimpleNamespace(round_args=lambda r: {"round": r},
                                is_stale=lambda sender, epoch: False)
    s.po_local = None if is_global else types.SimpleNamespace(van=van)
    s.po_global = types.SimpleNamespace(
        my_rank=0, num_servers=1, num_live_workers=lambda: parties,
        van=van)
    s._wan_trace = (-1, -1)
    return s


class RecordingGlobalWorker:
    """The party server's client of the global tier: keeps what is
    pushed and answers a push's callback with the responses it is given."""

    def __init__(self):
        self.pushed = []            # (kvs, g_rank, cb)
        self.responses = {}

    def push(self, kvs, g_rank, cb=None, **kw):
        assert kw["pull"] and kw["party_nsrv"] == 1
        self.pushed.append((kvs, g_rank, cb))

    def take_failure(self, ts):
        return None

    def take_response(self, ts):
        return self.responses.pop(ts)


def link_positions(kvs, i=0):
    """The positions of entry ``i`` of a sparse payload that a server
    handed the party-global link: coded (``compression.entries.CODED``,
    padded to whole words), decoded here as the receiver would."""
    aux = kvs.aux[i]
    assert aux.dtype == np.uint8 and aux.size % 4 == 0, aux.dtype
    return decode_positions(aux, np.asarray(kvs.vals[i]).size,
                            kvs.len_of(i))


def party_server_without_sockets(workers, global_servers=1, n=768,
                                 keys=None):
    """A party server of ``workers`` workers below ``global_servers``
    global servers that split every key evenly, ``keys`` (key -> its
    elements; ``KEY`` of ``n`` by default) initialized; Bi-Sparse 0.01
    on its forward."""
    s = server_without_sockets(1, False)
    s.has_global_tier = True
    s.cfg.bigarray_bound = 1 if global_servers > 1 else 1 << 40
    s.po_global.num_servers = global_servers
    s.po_local = types.SimpleNamespace(
        num_servers=1, num_live_workers=lambda: workers,
        van=s.po_local.van)
    s._wire = types.SimpleNamespace(enabled=lambda: False)
    s._wire_wan = s._transport = None
    s._fwd_tls = threading.local()
    s.worker_global = RecordingGlobalWorker()
    for key, size in ({KEY: n} if keys is None else keys).items():
        st = s._state(key, 0)
        st.stored = np.zeros(size, np.float32)
        st.length = st.total = size
        st.initialized = True
    return s


def party_batch_push(s, app, sender, ts, pushes, trace_round=-1):
    """One worker's combined push+pull of several keys in ONE message on
    the local tier, through the server's own ``_handle_data``: ``pushes``
    maps a key to its ``(values, positions)`` on the ``bsc`` wire. The
    rounds this completes go forward as one batch."""
    keys = list(pushes)
    sizes = [s._state(k, 0).total for k in keys]
    kvs = KVPairs(keys=keys, vals=[pushes[k][0] for k in keys],
                  aux=[pushes[k][1] for k in keys], offsets=[0] * len(keys),
                  totals=sizes, lens=sizes, compr="bsc")
    s._handle_data(push_req(sender, ts, "bsc", trace_round=trace_round),
                   kvs, app, False, False)
