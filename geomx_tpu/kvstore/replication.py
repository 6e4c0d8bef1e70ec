"""Durable server state: periodic incremental snapshots + peer replicas.

The reference never persists server state — a server death hands its slot
to a newcomer (van.cc:176-193) whose store is EMPTY, so training silently
resumes from re-initialized weights (SURVEY §5.4; the van.cc:224 TODO
leaves the global tier unrecovered entirely). This module closes that
gap for ``KVStoreDistServer``:

- a background thread ticks every ``PS_SNAPSHOT_INTERVAL`` seconds,
  collects the (key, shard-offset) states whose ``version`` moved since
  the last tick (dirty tracking — unchanged keys are never re-copied),
  merges them into an in-memory snapshot image and atomically rewrites
  ``PS_SNAPSHOT_DIR/geomx-<tier>-server-<rank>.snap`` (the msgpack
  codec + tmp-rename writer from ``checkpoint.py``);
- in multi-server tiers each tick also pushes the same dirty delta to
  the next-rank peer (``Command.REPLICA_UPDATE``), which accumulates a
  full replica image per sender — recovery without shared disks;
- a replacement server starting with ``is_recovery=True`` calls
  :meth:`restore` before serving: it reloads the snapshot file, or —
  when the disk image is missing (fresh host) — fetches the replica
  from its peer (``Command.REPLICA_FETCH``), repopulating parameters,
  round/version counters, the optimizer (hyper-parameters re-pickled,
  per-key slot states via the optimizer state codec) and the sync-mode
  flags. Resumed training continues from the pre-crash weights instead
  of re-init.

Recovery and snapshot activity is surfaced through ``profiler.instant``
events (``snapshot.write``, ``replica.push``, ``recovery.restore``) so a
chrome trace of a chaos run shows exactly when durability work happened.
"""

from __future__ import annotations

import copy
import json
import logging
import os
import pickle
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from geomx_tpu import checkpoint, profiler
from geomx_tpu.kvstore.base import Command
from geomx_tpu.ps import base as psbase
from geomx_tpu.ps import locks

log = logging.getLogger("geomx.replication")

# customer id of the server->server replica channel (0 = the KVServer,
# 1 = TSEngine hops, 2 = command rebroadcast)
_REPLICA_CID = 3


@locks.guarded_by("_lock", "_snap_versions", "_cache", "_replica_store",
                  "_last_updater_blob", "num_snapshots")
class ReplicationManager:
    """Snapshot/replica engine owned by one ``KVStoreDistServer``."""

    def __init__(self, server, cfg):
        self.server = server
        self.dir = cfg.snapshot_dir
        self.interval = max(float(cfg.snapshot_interval_s), 0.05)
        self.replicate = cfg.replicate
        self.enabled = bool(self.dir)
        # "snapshot" | "replica" | None — what restore() actually used;
        # tests assert on it to confirm recovery was NOT a re-init
        self.restored_from: Optional[str] = None
        self.num_snapshots = 0
        self._lock = locks.make_lock("ReplicationManager._lock")
        # (key, offset) -> last snapshotted version
        self._snap_versions: Dict[Tuple[int, int], int] = {}
        # merged snapshot image: (key, offset) -> entry dict
        self._cache: Dict[Tuple[int, int], dict] = {}
        # replica images held FOR peers: sender rank -> image
        self._replica_store: Dict[int, dict] = {}
        self._last_updater_blob = b""
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._kvw = None

    # -- identity --------------------------------------------------------

    def _po(self):
        """The overlay this server peers on: global servers replicate to
        other global servers, party/local servers to their tier's peers."""
        s = self.server
        return s.po_global if s.is_global_server and s.po_global is not None \
            else s.po_local

    def _tier(self) -> str:
        return "global" if self.server.is_global_server else "local"

    def path(self) -> str:
        return os.path.join(
            self.dir, f"geomx-{self._tier()}-server-{self._po().my_rank}.snap")

    def _peer_rank(self) -> Optional[int]:
        po = self._po()
        n = po.num_servers
        if n < 2 or not self.replicate:
            return None
        try:
            return (po.my_rank + 1) % n
        except Exception:  # noqa: BLE001 — van not started yet
            return None

    def _peer_kvw(self):
        if self._kvw is None:
            from geomx_tpu.ps.kv_app import KVWorker

            self._kvw = KVWorker(self._po(), customer_id=_REPLICA_CID)
            # Inbound REPLICA requests from the peer carry this same
            # customer_id, so they exact-match THIS customer in dispatch
            # (and would be silently dropped by a handler-less KVWorker)
            # instead of falling through to the KVServer.  Route them
            # into the server's command handler, mirroring how
            # worker_global doubles as a responder in server.py.
            global_tier = self._po() is self.server.po_global
            self._kvw.set_request_handle(
                lambda req, kvs, srv: self.server._handle(
                    req, kvs, srv, global_tier=global_tier))
        return self._kvw

    # -- lifecycle -------------------------------------------------------

    def start(self) -> None:
        # ticks run when there's SOMEWHERE durable to put state: a
        # snapshot dir, or (diskless multi-server tier) a peer replica
        if self._thread is not None:
            return
        if not self.enabled and self._peer_rank() is None:
            return
        self._thread = threading.Thread(
            target=self._loop, name="kv-snapshot", daemon=True)
        self._thread.start()

    def stop(self, flush: bool = True) -> None:
        """Stop the tick thread. ``flush=True`` (clean shutdown) writes a
        final snapshot; a FaultPlan crash passes False — a dead process
        gets no goodbye write, so recovery is exercised against whatever
        the last periodic tick persisted (real crash consistency)."""
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(5.0)
        if flush and t is not None:
            try:
                self.tick()
            except Exception:  # noqa: BLE001
                log.exception("final snapshot flush failed")

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — keep ticking
                log.exception("snapshot tick failed; thread kept")

    # -- snapshot side ---------------------------------------------------

    def _collect_dirty(self) -> Dict[Tuple[int, int], dict]:
        s = self.server
        with s._lock:
            items = list(s._states.items())
        out: Dict[Tuple[int, int], dict] = {}
        for (key, off), st in items:
            with st.lock:
                if not st.initialized or not st.has_store:
                    continue
                # _snap_versions is shared with _apply (restore path) and
                # guarded by self._lock there; taking it here too keeps
                # the pair ordered st.lock -> self._lock on both paths
                with self._lock:
                    if self._snap_versions.get((key, off), -1) == st.version:
                        continue
                    self._snap_versions[(key, off)] = st.version
                out[(key, off)] = {
                    "v": np.array(st.stored),
                    "total": int(st.total),
                    "version": int(st.version),
                    "rounds": int(st.rounds),
                }
        return out

    def _updater_blobs(self) -> Tuple[bytes, bytes]:
        """(pickled hyper-params, serialized per-key slot states).

        The updater is pickled WITHOUT its ``_states`` dict — pickling
        live state dicts races the update threads; ``_snapshot_states``
        copies them consistently under each key's lock instead."""
        upd = self.server.updater
        if upd is None:
            return b"", b""
        shell = copy.copy(upd)
        try:
            shell._states = {}
        except AttributeError:
            pass
        states = self.server._snapshot_states()
        return pickle.dumps(shell), checkpoint.serialize_states(states)

    def _flags(self) -> dict:
        s = self.server
        return {"sync_mode": bool(s.sync_mode),
                "sync_global_mode": bool(s.sync_global_mode),
                "multi_precision": bool(s.multi_precision)}

    def tick(self) -> int:
        """One snapshot pass; returns the number of dirty entries."""
        dirty = self._collect_dirty()
        upd_blob, upd_states = self._updater_blobs()
        # Serialize the image while still holding the lock: restore()'s
        # _apply mutates _cache and _last_updater_blob from the recovery
        # thread while the tick thread runs, so the old unlocked
        # read-serialize here could msgpack a dict mid-mutation (the
        # GX-L005 seed finding on _last_updater_blob). Disk I/O stays
        # outside the lock.
        with self._lock:
            upd_changed = upd_blob != self._last_updater_blob
            self._cache.update(dirty)
            if not self._cache and not upd_changed:
                return 0
            doc_blob = checkpoint.serialize_blob({
                "entries": checkpoint.serialize_states(self._cache),
                "updater": upd_blob,
                "updater_states": upd_states,
                "flags": self._flags(),
            }) if self.enabled else None
            n_total = len(self._cache)
        if doc_blob is not None:
            checkpoint._atomic_write(self.path(), doc_blob)
            with self._lock:
                self.num_snapshots += 1
            profiler.instant("snapshot.write", cat="recovery",
                             dirty=len(dirty), total=n_total)
        # only after a successful write (or with no snapshot dir at all)
        # so a failed _atomic_write retries the updater delta next tick
        with self._lock:
            self._last_updater_blob = upd_blob
        if dirty or upd_changed:
            self._push_to_peer(dirty, upd_blob if upd_changed else b"",
                               upd_states if upd_changed else b"")
        return len(dirty)

    def _push_to_peer(self, dirty: Dict, upd_blob: bytes,
                      upd_states: bytes) -> None:
        peer = self._peer_rank()
        if peer is None or (not dirty and not upd_blob):
            return
        body = json.dumps({
            "rank": self._po().my_rank,
            "entries": checkpoint.serialize_states(dirty).hex(),
            "updater": upd_blob.hex(),
            "updater_states": upd_states.hex(),
            "flags": self._flags(),
        })
        kvw = self._peer_kvw()
        try:
            ts = kvw.request(Command.REPLICA_UPDATE, body,
                             psbase.server_rank_to_id(peer))
            # short wait: a slow/stopping peer must not stall the tick
            # thread (or a clean shutdown's final flush) for long
            kvw.wait(ts, 5.0)
            profiler.instant("replica.push", cat="recovery",
                             peer=peer, dirty=len(dirty))
        except (TimeoutError, RuntimeError, OSError) as e:
            # a dead/slow peer must not stall snapshots; the next tick's
            # delta re-covers these keys only if they dirty again, but
            # the peer will full-resync when IT recovers us anyway
            log.warning("replica push to peer rank %d failed: %s", peer, e)

    # -- peer side (runs inside the server's command handler) ------------

    def accept_replica(self, body: str) -> None:
        d = json.loads(body)
        rank = int(d["rank"])
        entries = checkpoint.deserialize_states(bytes.fromhex(d["entries"]))
        with self._lock:
            img = self._replica_store.setdefault(
                rank, {"entries": {}, "updater": b"",
                       "updater_states": b"", "flags": {}})
            img["entries"].update(entries)
            if d.get("updater"):
                img["updater"] = bytes.fromhex(d["updater"])
                img["updater_states"] = bytes.fromhex(
                    d.get("updater_states", ""))
            img["flags"] = d.get("flags", img["flags"])

    def serve_replica(self, body: str) -> str:
        """Full replica image for a recovering peer, as a hex blob
        (empty string = nothing replicated here for that rank)."""
        rank = int(json.loads(body)["rank"])
        with self._lock:
            img = self._replica_store.get(rank)
            if img is None or not img["entries"]:
                return ""
            doc = {
                "entries": checkpoint.serialize_states(dict(img["entries"])),
                "updater": img["updater"],
                "updater_states": img["updater_states"],
                "flags": dict(img["flags"]),
            }
        return checkpoint.serialize_blob(doc).hex()

    # -- recovery side ---------------------------------------------------

    def _fetch_from_peer(self, timeout: float = 60.0) -> Optional[bytes]:
        peer = self._peer_rank()
        if peer is None:
            return None
        kvw = self._peer_kvw()
        try:
            ts = kvw.request(Command.REPLICA_FETCH,
                             json.dumps({"rank": self._po().my_rank}),
                             psbase.server_rank_to_id(peer))
            kvw.wait(ts, timeout)
            for resp in kvw.take_response_bodies(ts):
                if resp:
                    return bytes.fromhex(resp)
        except (TimeoutError, RuntimeError, OSError) as e:
            log.warning("replica fetch from peer rank %d failed: %s",
                        peer, e)
        return None

    def restore(self) -> Optional[str]:
        """Repopulate the server from its snapshot or a peer's replica —
        whichever is FRESHER (higher summed shard version).

        A snapshot is written on the periodic tick; the peer's replica
        advances every replicated round. After a crash the on-disk
        snapshot can therefore lag the replica by up to a tick interval
        — restoring it blindly (the old behavior) silently rolled those
        rounds back. Both candidates are deserialized and the higher
        version total wins; the snapshot wins ties (it is local and
        already includes the updater blob). The peer fetch uses a short
        timeout when a snapshot exists (best-effort upgrade) and the
        long one when the snapshot is the only hope.

        Called by ``KVStoreDistServer.start`` when either tier's van came
        up with ``is_recovery=True``, BEFORE ``_ready`` is set — no
        request is served from a half-restored store. Returns the source
        used ("snapshot"/"replica") or None (nothing to restore: the old
        volatile-store behavior, documented in tests/test_recovery.py)."""
        t0 = time.monotonic()
        check = getattr(self._po().van, "statecheck", None)
        if check is not None:
            check.on_restore("starting", self.server._ready.is_set())
        candidates = []  # (source, doc, entries), snapshot first
        if self.enabled and os.path.exists(self.path()):
            try:
                with open(self.path(), "rb") as f:
                    raw = f.read()
                doc = checkpoint.deserialize_blob(raw)
                candidates.append(
                    ("snapshot", doc,
                     checkpoint.deserialize_states(doc["entries"])))
            except (OSError, ValueError, KeyError) as e:
                log.warning("snapshot read failed (%s); trying peer", e)
        peer_blob = self._fetch_from_peer(
            timeout=5.0 if candidates else 60.0)
        if peer_blob is not None:
            try:
                doc = checkpoint.deserialize_blob(peer_blob)
                candidates.append(
                    ("replica", doc,
                     checkpoint.deserialize_states(doc["entries"])))
            except (ValueError, KeyError) as e:
                log.warning("peer replica unusable (%s)", e)
        if not candidates:
            log.info("recovery: no snapshot and no replica — store starts "
                     "empty (workers must re-init)")
            return None

        def freshness(cand):
            return sum(int(e.get("version", 0))
                       for e in cand[2].values())

        # max() keeps the FIRST maximal element: the snapshot on ties
        source, doc, entries = max(candidates, key=freshness)
        if len(candidates) == 2:
            log.info("recovery: snapshot version total %d vs replica %d "
                     "— restoring from %s",
                     freshness(candidates[0]), freshness(candidates[1]),
                     source)
        self._apply(doc, entries, source)
        dur_ms = (time.monotonic() - t0) * 1e3
        log.info("recovery: restored %d shard states from %s in %.1f ms",
                 len(entries), source, dur_ms)
        profiler.instant("recovery.restore", cat="recovery",
                         source=source, entries=len(entries),
                         ms=round(dur_ms, 2))
        self.restored_from = source
        return source

    def _apply(self, doc: dict, entries: Dict, source: str) -> None:
        s = self.server
        for (key, off), ent in entries.items():
            v = np.array(np.asarray(ent["v"]).ravel())
            st = s._state(key, off)
            with st.lock:
                st.stored = v
                st.length = v.size
                st.total = int(ent.get("total", 0)) or v.size
                st.dtype = v.dtype
                st.version = int(ent.get("version", 0))
                st.rounds = int(ent.get("rounds", 0))
                st.initialized = True
            with s._lock:
                s._key_total[key] = max(s._key_total.get(key, 0), st.total)
            with self._lock:
                self._snap_versions[(key, off)] = st.version
                self._cache[(key, off)] = ent
        flags = doc.get("flags") or {}
        if "sync_mode" in flags:
            s.sync_mode = bool(flags["sync_mode"])
        if "sync_global_mode" in flags:
            s.sync_global_mode = bool(flags["sync_global_mode"])
        if "multi_precision" in flags:
            s.multi_precision = bool(flags["multi_precision"])
        upd_blob = doc.get("updater") or b""
        if upd_blob:
            # deferred import: server.py imports this module at its top
            from geomx_tpu.kvstore.server import _safe_unpickle

            try:
                upd = _safe_unpickle(bytes(upd_blob))
                upd_states = doc.get("updater_states") or b""
                if upd_states:
                    upd.set_states(
                        checkpoint.deserialize_states(bytes(upd_states)))
                s.updater = upd
                # the tick thread compares-and-swaps this under the same
                # lock; an unlocked write here could lose either update
                with self._lock:
                    self._last_updater_blob = bytes(upd_blob)
            except Exception:  # noqa: BLE001 — params beat a dead updater
                log.exception("updater restore failed; workers must "
                              "re-ship the optimizer")
