"""KVStorePartyMesh — the mesh-party intra-DC tier (``dist_sync_mesh``).

Vanilla HiPS moves every gradient byte of a party over the LAN PS hop
(worker -> local server -> worker): PERF.md measures ~31 ms of host
protocol per round with a 9.5 ms combined-wire floor. But intra-party
the hardware already has ICI: the party's workers can form one JAX mesh
and aggregate gradients with a ``psum`` over the "dp" axis *inside* the
jitted train step — no host round-trip, no local-server push/pull, zero
van messages between members of the same party.

Topology (docs/mesh-party.md):

- the party's former van workers become ranks of one GSPMD mesh
  (``parallel.mesh.make_party_mesh``);
- exactly ONE mesh rank per party — the "global worker",
  ``jax.process_index() == 0`` — speaks the existing van to the party
  server (which keeps its raw-KVWorker forwarding role to the global
  tier), reusing :class:`KVStoreDist`'s combined wire, P3 slicing, BSC,
  quantized wire codecs (``GEOMX_WIRE_CODEC`` — the inner store's
  :class:`compression.device.WireCodec` and its error-feedback
  residuals live on this one van-speaking rank), membership epochs and
  trace stamping unchanged. The party cfg says ``num_workers=1``: the
  van sees one worker per party;
- results are broadcast back into the mesh as replicated device arrays
  (``device_put`` with a replicated NamedSharding); BSC top-k selection
  and residual feedback compute device-side (trainer_device) so only
  the global worker materializes host arrays — geomx-lint GX-J104
  rejects unguarded host transfers on a mesh round path.

Mesh-tier collectives never touch the van, so their bytes get their own
counter family (``mesh.bytes{tier=mesh,...}``, from array shapes, per
round) and :func:`telemetry.wan_bytes` structurally excludes them.

Round aborts fan out: when the inner store's round dies (remote server
crash, membership epoch bump, blown resend deadline), every live
RoundFuture issued through this store is failed immediately
(``RoundFuture.abort_pending``) so mesh ranks joining on other keys
never sit out op_timeout on a round that cannot complete.
"""

from __future__ import annotations

import weakref
from typing import Dict, Optional

from geomx_tpu import config as cfg_mod
from geomx_tpu import telemetry
from geomx_tpu.kvstore.base import KVStore
from geomx_tpu.kvstore.dist import KVStoreDist
from geomx_tpu.kvstore.frontier import RoundFuture


def _ring_bytes(party_size: int, nbytes: int) -> int:
    """Link bytes of one ring all-reduce of ``nbytes`` over the party:
    each of P devices sends 2*(P-1) chunks of nbytes/P — summed over
    links that is 2*(P-1)*nbytes. Counted from shapes, not measured:
    the point is an honest per-round magnitude for the mesh tier, kept
    out of wan_bytes() by construction."""
    return 2 * max(0, party_size - 1) * int(nbytes)


def maybe_init_multihost(cfg) -> bool:
    """``jax.distributed.initialize`` from the GEOMX_MESH_* env knobs.

    Returns True when this process joined a multi-process mesh (after
    which ``jax.process_index()`` is real and picks the global worker).
    No-ops on single-process runs (the knobs unset) and on repeat calls.
    """
    if not cfg.mesh_coordinator or cfg.mesh_num_processes <= 1:
        return False
    import jax

    if jax.distributed.is_initialized():
        return True   # idempotent re-entry
    jax.distributed.initialize(
        coordinator_address=cfg.mesh_coordinator,
        num_processes=cfg.mesh_num_processes,
        process_id=cfg.mesh_process_id)
    return True


class KVStorePartyMesh(KVStore):
    def __init__(self, sync_global: bool = True,
                 cfg: Optional[cfg_mod.Config] = None,
                 mesh=None, party_index: int = 0):
        super().__init__()
        self.cfg = cfg or cfg_mod.load()
        # multi-host ICI (run_mesh_multihost.sh): join the process group
        # BEFORE building the mesh so jax.devices()/process_index() see
        # the whole party
        maybe_init_multihost(self.cfg)
        if mesh is None:
            from geomx_tpu.parallel.mesh import make_party_mesh

            mesh = make_party_mesh(self.cfg.party_mesh_size, party_index)
        self.mesh = mesh
        self.party_size = int(mesh.devices.size)
        import jax

        # single-controller per party in-process; on multi-host meshes
        # process 0 of the party is the van speaker
        self._is_global_worker = jax.process_index() == 0
        # quantized mesh collective (GEOMX_MESH_CODEC): "none" keeps the
        # fused GSPMD psum byte-for-byte; other codecs route gradient
        # all-reduces through the quantized ppermute ring, one stateful
        # reducer (= one set of error-feedback residual streams) per key
        from geomx_tpu.compression.device import MESH_CODECS

        self.mesh_codec = self.cfg.mesh_codec or "none"
        if self.mesh_codec not in MESH_CODECS:
            raise ValueError(
                f"GEOMX_MESH_CODEC={self.mesh_codec!r}: expected one of "
                f"{MESH_CODECS}")
        self.mesh_block = int(self.cfg.mesh_block)
        self._reducers: Dict = {}
        # trainers holding their own device-resident ring residuals
        # (DeviceResidentTrainer threads them through its jitted step)
        # register here so abort recovery zeroes EVERY residual stream,
        # not just the store-keyed reducers
        self._residual_reset_hooks: list = []
        # the party's ONLY van-speaking worker: the shell reuses the
        # whole wire/membership/trace machinery unchanged
        self.inner = KVStoreDist(sync_global=sync_global, cfg=self.cfg)
        self._live_futs: "weakref.WeakSet[RoundFuture]" = weakref.WeakSet()
        self.inner.round_abort_hook = self._fail_fast_pending

    # -- identity --------------------------------------------------------

    @property
    def type(self) -> str:
        return "dist_sync_mesh"

    @property
    def is_global_worker(self) -> bool:
        return self._is_global_worker

    @property
    def rank(self) -> int:
        return self.inner.rank

    @property
    def num_workers(self) -> int:
        return self.inner.num_workers

    @property
    def num_all_workers(self) -> int:
        return self.inner.num_all_workers

    @property
    def is_master_worker(self) -> bool:
        return self.inner.is_master_worker

    @property
    def po(self):
        return self.inner.po

    def membership_epoch(self) -> int:
        return self.inner.membership_epoch()

    def get_num_dead_node(self, role=None) -> int:
        return self.inner.get_num_dead_node(role)

    def notify_round(self, round_idx: int) -> None:
        self.inner.notify_round(round_idx)

    # -- mesh side -------------------------------------------------------

    def replicated_sharding(self):
        from geomx_tpu.parallel.mesh import replicated

        return replicated(self.mesh)

    def batch_sharding(self):
        from geomx_tpu.parallel.mesh import batch_sharded

        return batch_sharded(self.mesh)

    def put_replicated(self, tree):
        """Broadcast host/device leaves into the mesh (the "results back
        into the mesh" leg: one replicated device_put, no van traffic)."""
        import jax

        return jax.device_put(tree, self.replicated_sharding())

    def shard_batch(self, *arrays):
        """Split batch arrays over the party's dp axis (``None`` passes
        through — e.g. an unused label operand)."""
        import jax

        sh = self.batch_sharding()
        out = tuple(a if a is None else jax.device_put(a, sh)
                    for a in arrays)
        return out[0] if len(out) == 1 else out

    def ring_reducer(self, key, n: int, mean: bool = False):
        """The per-key quantized ring reducer (residual lifecycle lives
        here: one reducer = one set of error-feedback streams per key,
        never mixed across keys, rebuilt when an elastic resize changes
        the vector length). None when the codec is "none" — callers
        keep the fused-psum path untouched."""
        if self.mesh_codec == "none":
            return None
        from geomx_tpu.parallel.quant_collectives import QuantRingReducer

        n = int(n)
        red = self._reducers.get(key)
        if red is None or red.n != n or red.mean != bool(mean):
            red = QuantRingReducer(
                self.mesh, self.mesh_codec, n, block=self.mesh_block,
                threshold=self.cfg.wire_2bit_threshold, mean=mean)
            self._reducers[key] = red
        return red

    def register_residual_reset_hook(self, fn) -> None:
        """Callback run by :meth:`reset_mesh_residuals` — for trainers
        that thread their OWN ring residual through the jitted step
        instead of borrowing a store-keyed reducer."""
        self._residual_reset_hooks.append(fn)

    def reset_mesh_residuals(self) -> None:
        """Zero every key's ring residual streams — abort/membership
        recovery re-seeds from zero rather than replaying stale error
        (the WireCodec.reset policy applied to the mesh tier; an abort
        loses at most the one drained quantized step)."""
        for red in self._reducers.values():
            red.reset()
        for fn in self._residual_reset_hooks:
            fn()

    def count_collective(self, nbytes: int, op: str = "psum",
                         n_msgs: int = 1) -> None:
        """Account one fused mesh collective of ``nbytes`` fp32 payload
        under the tier=mesh counter family (never tier=global:
        wan_bytes() must stay honest about what actually crossed the
        WAN). With a quantized codec the ring model counts what the
        hops actually move — codes plus the exponent/threshold sidecar
        — under its own codec= label."""
        if self.mesh_codec == "none":
            wire = _ring_bytes(self.party_size, nbytes)
        else:
            from geomx_tpu.parallel.quant_collectives import ring_wire_bytes

            wire = ring_wire_bytes(self.mesh_codec, int(nbytes) // 4,
                                   self.party_size, self.mesh_block)
        telemetry.counter_inc("mesh.bytes", wire, tier="mesh", op=op,
                              codec=self.mesh_codec)
        telemetry.counter_inc("mesh.messages", n_msgs, tier="mesh", op=op)

    def record_round_collectives(self, leaves, op: str = "psum") -> None:
        """Count one round's worth of gradient psums from array shapes
        (XLA fuses them into the jitted step, so shapes are the only
        honest source of per-round collective volume). Shape metadata
        only — this must never materialize a leaf on the host
        (GX-J104: it runs on every mesh rank's round path)."""
        nbytes = 0
        for leaf in leaves:
            nbytes += int(getattr(leaf, "nbytes", 0))
        self.count_collective(nbytes, op=op)

    # -- round-abort fan-out ---------------------------------------------

    def _fail_fast_pending(self, reason: str) -> None:
        """round_abort_hook on the inner store: the van round is dead —
        fail every pending key of every live future NOW so mesh ranks
        joining elsewhere unblock with RoundAborted instead of hanging
        out op_timeout (give_up_exc maps "round aborted" to
        RoundAborted, which the trainer's re-issue loop handles)."""
        for fut in list(self._live_futs):
            fut.abort_pending(f"round aborted: {reason}")
        # the aborted round's drained quantized step is lost; stale
        # error must not replay into the retried round
        self.reset_mesh_residuals()

    def _watch(self, fut: RoundFuture) -> RoundFuture:
        self._live_futs.add(fut)
        return fut

    # -- data plane (van traffic — global worker only) -------------------

    def _require_global(self, opname: str) -> None:
        if not self._is_global_worker:
            raise RuntimeError(
                f"{opname}: only the party's global worker speaks the "
                f"van; non-global mesh ranks aggregate via device "
                f"collectives only")

    def init(self, key, value) -> None:
        if self.is_global_worker:
            self.inner.init(key, value)

    def push(self, key, value, priority: int = 0) -> None:
        self._require_global("push")
        self.inner.push(key, value, priority=priority)

    def pull(self, key, out=None, priority: int = 0):
        self._require_global("pull")
        return self.inner.pull(key, out=out, priority=priority)

    def push_pull(self, key, value, out, priority: int = 0) -> None:
        self._require_global("push_pull")
        self.inner.push_pull(key, value, out, priority=priority)

    def push_pull_async(self, key, value, out, priority: int = 0,
                        slice_bytes: Optional[int] = None) -> RoundFuture:
        self._require_global("push_pull_async")
        return self._watch(self.inner.push_pull_async(
            key, value, out, priority=priority, slice_bytes=slice_bytes))

    def push_pull_bsc_batch(self, keys, values_list, indices_list,
                            priority: int = 0, timeout: float = None):
        self._require_global("push_pull_bsc_batch")
        return self.inner.push_pull_bsc_batch(
            keys, values_list, indices_list, priority=priority,
            timeout=timeout)

    def push_pull_bsc_batch_async(self, keys, values_list, indices_list,
                                  priority: int = 0,
                                  slice_bytes: Optional[int] = None
                                  ) -> RoundFuture:
        self._require_global("push_pull_bsc_batch_async")
        return self._watch(self.inner.push_pull_bsc_batch_async(
            keys, values_list, indices_list, priority=priority,
            slice_bytes=slice_bytes))

    def wait(self, keys=None, timeout: float = None) -> None:
        if self.is_global_worker:
            self.inner.wait(keys, timeout=timeout)

    waitall = wait

    # -- control plane ---------------------------------------------------

    def set_optimizer(self, optimizer) -> None:
        self._require_global("set_optimizer")
        self.inner.set_optimizer(optimizer)

    def set_gradient_compression(self, compression_params: Dict) -> None:
        super().set_gradient_compression(compression_params)
        if self.is_global_worker:
            self.inner.set_gradient_compression(compression_params)

    def set_multi_precision(self, multi_precision: bool = True) -> None:
        if self.is_global_worker:
            self.inner.set_multi_precision(multi_precision)

    def save_optimizer_states(self, fname: str) -> None:
        self._require_global("save_optimizer_states")
        self.inner.save_optimizer_states(fname)

    def load_optimizer_states(self, fname: str) -> None:
        self._require_global("load_optimizer_states")
        self.inner.load_optimizer_states(fname)

    def metrics(self, timeout: float = 30.0) -> Dict[str, object]:
        self._require_global("metrics")
        return self.inner.metrics(timeout=timeout)

    def barrier(self, is_global: bool = False) -> None:
        if self.is_global_worker:
            self.inner.barrier(is_global=is_global)

    def close(self) -> None:
        self.inner.close()
