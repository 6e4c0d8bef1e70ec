"""Device-resident sparse trainer: params never leave the accelerator.

The TPU-native flagship worker loop for the BASELINE.md target config
(HiPS + Bi-Sparse). The plain ``Trainer`` round-trips every parameter
and gradient through host memory each step — fine when the chip is
PCIe-local, ruinous when it is not, and wasteful everywhere. Here the
parameters stay resident on the device as one flat fp32 vector and the
host<->device link carries only:

- down: the PER-KEY BSC-selected (values, indices) of the
  momentum-corrected gradient (exact top-k per tensor on device,
  matching the reference's per-tensor compression — reference
  semantics: gradient_compression.cc:191 BSCompress runs per key — and
  found without a sort: ``ops.select`` counts its way to each key's
  k-th magnitude and compacts what is at or over it, keys of one size
  under one traced body);
- up: the nonzeros of the aggregated gradient pulled back from the
  HiPS tier (bounded by workers x k), as one fixed-size padded array so
  the jitted apply never retraces.

The packed wire is an INT32 array: float payloads (loss, values) are
bitcast int32-wards (lax.bitcast_convert_type) and indices ride as
native int32, so any index a flat int32 can address is exact — models
up to 2^31 parameters per trainer (the round-3 float32 mantissa packing
capped this at 2^24). The direction of the bitcast is load-bearing: the
round-4 chip capture collapsed to chance accuracy because the inverse
packing (indices bitcast INTO a float32 array) produces denormal bit
patterns for every index < 2^23, and TPU float data movement inside jit
(the concatenate fusing through the VPU) flushes denormals to zero —
every scatter landed on coordinate 0. Integer lanes never flush, so the
int32 packing is bit-exact on every backend (probe:
tools/chip_sanity.py transfer_bitexact / bitcast_in_jit).

The round is one verb of the store, ``push_pull_bsc_batch_async``: the
trainer hands it each chunk's per-key selection and applies the
aggregate the returned ``RoundFuture`` completes with. HOW a selection
is aggregated is the store's business: ``KVStoreDist`` (and the mesh
party store around it) sends one combined message per (chunk, server) —
O(k) bytes and host work per key; the single-process "local" store
answers on the spot with the selection itself.

KVStore semantics follow examples/cnn_bsc.py: the PS tier is an
AGGREGATOR (no server-side optimizer); every worker applies the same
optimizer step locally on the identical aggregated sparse gradient, so
replicas stay bit-identical without shipping weights. Worker pushes are
scaled by 1/num_workers so the aggregated sum is the mean gradient.

The local optimizer is SGD (+momentum) as a jitted sparse-aware update:
momentum state is dense on device; untouched coordinates still decay,
touched ones get the aggregated gradient (dense-momentum-on-sparse-
grads, the standard treatment).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, Sequence

import numpy as np

from geomx_tpu import profiler, runtime, telemetry
from geomx_tpu.kvstore.frontier import (plan_chunks,
                                        slice_bytes_from_shape)
from geomx_tpu.ops import expand
from geomx_tpu.ops.select import kernel_keys, leaving, topk_flat

__all__ = ["DeviceResidentTrainer"]


class DeviceResidentTrainer:
    def __init__(self, params: Sequence[Any], kvstore,
                 grad_fn: Callable, threshold: float = 0.01,
                 learning_rate: float = 0.01, momentum: float = 0.0,
                 begin_key: int = 0):
        """``params``: list of array leaves (key of leaf i =
        ``begin_key + i``); ``grad_fn(leaf_list, X, y) -> (loss,
        grad_leaves)`` must be jit-compatible (it is traced into the
        fused device step). A ``grad_fn`` may carry ``counted``, a pair
        ``(telemetry counter names, fn)`` with ``fn(leaf_list, X, y) ->
        (loss, grad_leaves, counts)``, one float32 count per name: the
        step then runs ``fn``, the counts come down with the loss, in
        the transfer the round makes anyway, and are booked once a
        round (exact below 2^24).

        The local optimizer is deliberately SGD on the aggregated
        selection: BSC's residual feedback DELIVERS accumulated
        gradients (the v-buffer sums until a coordinate is selected),
        so plain SGD applies each coordinate's full accumulated mass;
        heavy-ball momentum compounds with the u-buffer's own 0.9
        momentum correction and diverges, and per-coordinate adaptive
        optimizers (Adam) see each coordinate only ~threshold*rounds
        times so their moment estimates starve (both measured on the
        demo CNN; SGD at lr 0.05 converges on every platform there)."""
        import jax
        import jax.numpy as jnp

        self.kv = kvstore
        self._aux_names, grad_fn = getattr(grad_fn, "counted",
                                           ((), grad_fn))
        self.begin_key = begin_key
        self.threshold = threshold
        self.learning_rate = learning_rate
        self.momentum = momentum
        # mesh-party store (kvstore.mesh_party): trainer state lives
        # replicated on the party mesh, batches shard over "dp", and
        # grad_fn's mean-loss backward gets an XLA-inserted psum — the
        # party's aggregation happens inside the jitted step, so the
        # BSC selection below runs on the party-MEAN gradient and the
        # van carries one worker's traffic per party. num_all_workers
        # is then the number of parties, so the g/nw scaling already
        # matches the wire path's per-member scaling.
        self._mesh = getattr(kvstore, "mesh", None)

        leaves = [np.asarray(p, np.float32) for p in params]
        self._shapes = [l.shape for l in leaves]
        self._sizes = [int(l.size) for l in leaves]
        self._offsets = np.concatenate(
            [[0], np.cumsum(self._sizes)]).astype(np.int64)
        self.total = int(self._offsets[-1])
        if self.total >= 1 << 31:
            raise ValueError("DeviceResidentTrainer addresses elements "
                             f"with int32: < 2^31 params, got {self.total}")
        # per-key top-k (reference per-tensor BSC: every tensor keeps
        # ceil(size * threshold) coordinates, minimum 1)
        self._ks = [max(int(sz * threshold), 1) for sz in self._sizes]
        self.k = sum(self._ks)
        self._kofs = np.concatenate([[0], np.cumsum(self._ks)]).astype(
            np.int64)

        # kv bootstrap: init + pull once (the only full-weight transfer)
        for i, leaf in enumerate(leaves):
            self.kv.init(begin_key + i, leaf)
        if not getattr(self.kv, "is_master_worker", False):
            for i in range(len(leaves)):
                self.kv.pull(begin_key + i, out=leaves[i])
        self.kv.wait()

        repl = (kvstore.replicated_sharding() if self._mesh is not None
                else None)

        def dput(x):
            return jax.device_put(x, repl) if repl is not None \
                else jax.device_put(x)

        flat0 = np.concatenate([l.ravel() for l in leaves])
        self._flat = dput(jnp.asarray(flat0))
        self._u = dput(jnp.zeros(self.total, jnp.float32))
        self._v = dput(jnp.zeros(self.total, jnp.float32))
        self._mom = (dput(jnp.zeros(self.total, jnp.float32))
                     if momentum else None)

        shapes = self._shapes
        bounds = list(self._offsets[1:-1])
        offsets = [int(o) for o in self._offsets[:-1]]
        sizes, ks = self._sizes, self._ks
        # scale by the TOTAL worker count across parties (the global
        # tier sums every party's aggregate), not the party-local count
        nw = max(int(getattr(self.kv, "num_all_workers", 0)
                     or getattr(self.kv, "num_workers", 1)), 1)

        # quantized combined wire: when a wire codec is active the store
        # ships the selected values as float16 ("bsc16"). Fuse the
        # narrowing into the device step with error feedback — the fp16
        # rounding error goes BACK into the residual v instead of being
        # dropped on the host cast, so the wire's astype(float16) in
        # dist._prepare_bsc_shards is exactly lossless
        kcfg = getattr(self.kv, "cfg", None)
        wire16 = bool(getattr(kcfg, "wire_codec", ""))

        # quantized mesh collective (GEOMX_MESH_CODEC != "none"): the
        # party aggregate moves off the XLA-inserted fp32 psum and onto
        # the explicit quantized ppermute ring — set up below, after
        # the shared BSC body is defined
        mesh_codec = (getattr(kcfg, "mesh_codec", "none") or "none") \
            if self._mesh is not None else "none"
        self._mesh_quant = mesh_codec != "none"
        # what the ops that have a kernel form are told (a local: the
        # jitted programs must not hold the trainer)
        mesh = self._mesh

        def _grad_cat(flat, X, y):
            lv = [p.reshape(s) for p, s in
                  zip(jnp.split(flat, bounds), shapes)]
            loss, grads, *counts = grad_fn(lv, X, y)
            if counts:
                # "loss" is from here on the head of the download: the
                # loss, then the counts. Without counts nothing changes:
                # the program stays, instruction for instruction, the one
                # the compile cache already holds
                loss = jnp.concatenate(
                    [jnp.reshape(a, (-1,)).astype(jnp.float32)
                     for a in (loss, *counts)])
            return loss, jnp.concatenate([gg.reshape(-1) for gg in grads])

        def _bsc(loss, g, u, v):
            # BSC: momentum-corrected accumulation, exact per-key top-k
            # (reference: gradient_compression.cc:191-268, per tensor)
            u = 0.9 * u + g
            v = v + u
            # model-flat positions, ascending and distinct (keys in
            # flat order, each key's ascending), v there, and each
            # key's rule of membership
            idx, vals, rules = topk_flat(v, offsets, sizes, ks, mesh=mesh)
            # the selected leave u and v in one dense masked pass over
            # both, in place, where two scatters wrote the k positions
            # one by one. The mask (a byte an element) is written a key
            # at a time, the keys of one size under one traced body
            with jax.named_scope("bsc_reset"):
                gone = jnp.zeros(v.shape, bool)
                for members, t, cut in rules:
                    size = sizes[members[0]]
                    at = jnp.asarray([offsets[i] for i in members],
                                     jnp.int32)

                    def mark(j, gone):
                        seg = jax.lax.dynamic_slice(v, (at[j],), (size,))
                        return jax.lax.dynamic_update_slice(
                            gone, leaving(seg, t[j], cut[j]), (at[j],))

                    gone = jax.lax.fori_loop(0, len(members), mark, gone)
                u = jnp.where(gone, 0.0, u)
                if wire16:
                    # a selected coordinate keeps what the narrowing
                    # drops as its residual (instead of resetting to
                    # zero): it rides into the next round's accumulation
                    narrowed = v.astype(jnp.float16).astype(jnp.float32)
                    v = jnp.where(gone, v - narrowed, v)
                    vals = vals.astype(jnp.float16).astype(jnp.float32)
                else:
                    v = jnp.where(gone, 0.0, v)
            return loss, vals, idx, u, v

        def select(flat, u, v, X, y):
            loss, g = _grad_cat(flat, X, y)
            return _bsc(loss, g / nw, u, v)

        # -- the round's chunks (P3_SLICE_BYTES) ------------------------
        # keys group in layer order into ~P3_SLICE_BYTES wire-byte
        # chunks (~8 bytes per selected element); each chunk's D2H
        # fetch, async combined round and jitted dynamic_update_slice
        # apply flow independently — chunk i applies while chunk i+1's
        # bytes are still on the wire. Where nothing was asked for (0)
        # the budget is the declared party-global link's bandwidth-delay
        # product: a link's two directions then carry pushes and answers
        # at once, where one chunk holds each idle while the other
        # works. No declared link = one chunk: one message per server
        # per round. The budget is this plan's alone: keys stay whole,
        # the store and the servers shard as their configuration says.
        budget = int(getattr(kcfg, "p3_slice_bytes", 0))
        if budget == 0 and getattr(kcfg, "shape_plan", ""):
            budget = slice_bytes_from_shape(kcfg)
        chunks = plan_chunks(list(range(len(sizes))),
                             [8 * kk for kk in ks], budget)
        self._chunks = chunks
        # per chunk: selection range, flat param range, upload cap —
        # chunk key runs are contiguous, so each covers one flat
        # slice [flo, flo+fsize) and the slices partition [0, total);
        # the aggregate has <= nw*k nonzeros, and padding the upload to
        # that FIXED size keeps one compiled apply per chunk (a shape
        # that varied per round would retrace/recompile every step)
        meta = []
        for ch in chunks:
            a, b = ch.items[0], ch.items[-1]
            sel_lo, sel_hi = int(self._kofs[a]), int(self._kofs[b + 1])
            flo, fhi = int(self._offsets[a]), int(self._offsets[b + 1])
            fsize, cap = fhi - flo, nw * (sel_hi - sel_lo)
            if fsize + cap >= 1 << 31:
                # an upload's pad positions run from fsize to fsize+cap
                raise ValueError("a chunk's elements and upload slots "
                                 f"together must stay under 2^31: {ch}")
            meta.append((sel_lo, sel_hi, flo, fsize, cap))
        self._chunk_meta = meta
        # one upload a chunk, held for the trainer's life and written in
        # place by _chunk_up (which says when it may be): all pad until a
        # round fills it, value 0.0 in every slot and slot s at position
        # fsize + s, past the chunk's end whatever a round leaves over
        self._uploads = []
        for _lo, _hi, _flo, fsize, cap in meta:
            up = np.empty(2 * cap, np.int32)
            up[:cap] = 0
            up[cap:] = np.arange(fsize, fsize + cap, dtype=np.int32)
            self._uploads.append(up)
        self._filled = [0] * len(meta)  # slots each one's last round wrote
        # the chunks whose apply expands its upload by the kernel of
        # ops/expand.py (its rule: a TPU backend, no mesh, a long list)
        self._expand_applies = sum(
            expand.runs_kernel(jax.ShapeDtypeStruct((cap,), jnp.int32), mesh)
            for *_, cap in meta)
        # and the keys whose selection compacts by the kernel of
        # ops/select.py (its rule: the same, for the size groups that
        # are selected one key after the other)
        self._select_kernel_keys = len(kernel_keys(
            jax.ShapeDtypeStruct((self.total,), jnp.float32), sizes, ks,
            mesh))
        sel_bounds = [(m[0], m[1]) for m in meta]

        # u and v are donated: the round rebinds both from the outputs,
        # and at 248M parameters two more flat vectors beside two
        # trainers' state do not fit a 16 GB chip
        @partial(jax.jit, donate_argnums=(1, 2))
        def fwd_chunks(flat, u, v, X, y):
            loss, vals, idx, u, v = select(flat, u, v, X, y)
            # one packed int32 array PER CHUNK so the host can fetch
            # and dispatch each chunk independently; loss rides
            # separately (fetching its value fences the program)
            packs = tuple(
                jnp.concatenate(
                    [jax.lax.bitcast_convert_type(vals[lo:hi],
                                                  jnp.int32),
                     idx[lo:hi]])
                for lo, hi in sel_bounds)
            return loss.astype(jnp.float32), packs, u, v

        @partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0, 1))
        def apply_chunk(flat, mom, up, flo, fsize):
            # up layout (see _chunk_up): [vals(cap) bitcast i32,
            # idx(cap) CHUNK-relative]. The positions ascend and are
            # distinct (the aggregate is sorted unique entries, keys in
            # flat order), the pad slots ascend on from the chunk's end
            # or past it and drop: what ops/expand.py asks of a list, to
            # make it the chunk's dense update without a sort and, where
            # its kernel runs, without a scatter
            cap = up.shape[0] // 2
            vals = jax.lax.bitcast_convert_type(up[:cap], jnp.float32)
            cidx = up[cap:]
            with jax.named_scope("apply_expand"):
                g = expand.dense_from_sorted(vals, cidx, fsize, mesh=mesh)
            seg = jax.lax.dynamic_slice(flat, (flo,), (fsize,))
            if mom is None:
                return (jax.lax.dynamic_update_slice(
                    flat, seg - learning_rate * g, (flo,)), None)
            mseg = jax.lax.dynamic_slice(mom, (flo,), (fsize,))
            mseg = momentum * mseg + g
            return (jax.lax.dynamic_update_slice(
                        flat, seg - learning_rate * mseg, (flo,)),
                    jax.lax.dynamic_update_slice(mom, mseg, (flo,)))

        self._fwd_chunks = fwd_chunks
        self._apply_chunk = apply_chunk

        # -- quantized mesh collective (GEOMX_MESH_CODEC) ----------------
        # The psum XLA inserts for the dp-sharded mean loss moves the
        # dense fp32 gradient; with a codec the party aggregate becomes
        # an explicit shard_map: each rank takes the grad of its LOCAL
        # shard's mean loss, the quantized ppermute ring sums across
        # ranks (error-feedback residual threaded through the jitted
        # step), and /P restores the party mean the psum produced. The
        # ring output is bit-identical on every rank by construction,
        # so the BSC selection downstream stays replica-coherent.
        if self._mesh_quant:
            from jax.sharding import NamedSharding

            from geomx_tpu.parallel import quant_collectives as qc
            from geomx_tpu.parallel.mesh import P as _P

            psize = int(self._mesh.shape["dp"])
            mesh_block = int(getattr(kcfg, "mesh_block", 256) or 256)
            thr = float(getattr(kcfg, "wire_2bit_threshold", 0.5))
            self._mesh_size = psize
            self._mesh_codec = mesh_codec
            self._mesh_block = mesh_block
            # captured HERE so _reset_mesh_residual never imports on a
            # handler thread (round_abort_hook runs on the van side and
            # infra threads can hold the package import lock)
            mesh0 = self._mesh

            def _zero_res():
                return jax.device_put(
                    qc.zero_residual(psize, self.total, mesh_codec,
                                     mesh_block),
                    NamedSharding(mesh0, _P("dp")))

            self._zero_mesh_res = _zero_res

            def _mesh_grad_body(flat, X, y, res):
                loss, gl = _grad_cat(flat, X, y)
                gs, new_res = qc.ring_all_reduce(
                    gl, res[0], size=psize, axis_name="dp",
                    codec=mesh_codec, block=mesh_block, threshold=thr)
                loss = jax.lax.psum(loss, "dp")
                # the loss is the ranks' mean, the counts their sum
                loss = loss.at[0].divide(psize) if self._aux_names \
                    else loss / psize
                return loss, gs / psize, new_res[None]

            mesh_grad = jax.shard_map(
                _mesh_grad_body, mesh=self._mesh,
                in_specs=(_P(), _P("dp"), _P("dp"), _P("dp")),
                out_specs=(_P(), _P(), _P("dp")), check_vma=False)

            def select_q(flat, u, v, X, y, res):
                loss, g, res = mesh_grad(flat, X, y, res)
                loss, vals, idx, u, v = _bsc(loss, g / nw, u, v)
                return loss, vals, idx, u, v, res

            @partial(jax.jit, donate_argnums=(1, 2))
            def fwd_chunks_q(flat, u, v, X, y, res):
                loss, vals, idx, u, v, res = select_q(flat, u, v,
                                                      X, y, res)
                packs = tuple(
                    jnp.concatenate(
                        [jax.lax.bitcast_convert_type(vals[lo:hi],
                                                      jnp.int32),
                         idx[lo:hi]])
                    for lo, hi in sel_bounds)
                return loss.astype(jnp.float32), packs, u, v, res

            self._fwd_chunks_q = fwd_chunks_q
            self._reset_mesh_residual()
            # abort recovery zeroes this trainer's residual along with
            # the store-keyed reducers
            if hasattr(self.kv, "register_residual_reset_hook"):
                self.kv.register_residual_reset_hook(
                    self._reset_mesh_residual)

    def _reset_mesh_residual(self) -> None:
        """(Re-)seed the ring's error-feedback streams at zero — round
        aborts must not replay stale error into the retried round.
        Import-free: safe from the store's round_abort_hook (which runs
        on van/handler threads)."""
        if not self._mesh_quant:
            return
        self._mesh_res = self._zero_mesh_res()

    def _book(self, head: np.ndarray) -> float:
        """The loss from the head of a download; grad_fn's counts, if
        any, go to their telemetry counters, and so do the number of
        keys this round selected by threshold and reset in a dense
        masked pass (every key: neither has a second path), the number
        of chunks the round went out in, how many of them the apply
        expands by the kernel and how many keys' selections compact by
        theirs."""
        telemetry.counter_inc("step.select_threshold_keys",
                              len(self._sizes))
        telemetry.counter_inc("step.select_kernel_keys",
                              self._select_kernel_keys)
        telemetry.counter_inc("step.dense_reset_keys", len(self._sizes))
        telemetry.counter_inc("trainer.round_chunks", len(self._chunks))
        telemetry.counter_inc("trainer.expand_applies", self._expand_applies)
        head = np.atleast_1d(head)
        for name, value in zip(self._aux_names, head[1:]):
            telemetry.counter_inc(name, float(value))
        return float(head[0])

    def _run_fwd_chunks(self, X, y):
        """Run the device step, advancing (u, v) and — on the quantized
        mesh path — the ring residual."""
        if self._mesh_quant:
            loss_d, packs, self._u, self._v, self._mesh_res = \
                self._fwd_chunks_q(self._flat, self._u, self._v,
                                   X, y, self._mesh_res)
        else:
            loss_d, packs, self._u, self._v = self._fwd_chunks(
                self._flat, self._u, self._v, X, y)
        return loss_d, packs

    def _place_batch(self, X, y):
        """Mesh mode: shard the batch over the party's dp axis (the
        psum in grad_fn's backward then aggregates across mesh ranks);
        elsewhere a no-op. Mesh rounds must run on the party's global
        worker — it is the only rank allowed to materialize host
        arrays (GX-J104) and speak the van."""
        if self._mesh is None:
            return X, y
        if not getattr(self.kv, "is_global_worker", True):
            raise RuntimeError(
                "DeviceResidentTrainer mesh rounds drive the party "
                "from its global worker; non-global mesh ranks hold "
                "no host-side round state")
        return self.kv.shard_batch(X, y)

    def _count_mesh_round(self) -> None:
        """Account one round's intra-party collective volume: the dp
        psum XLA inserts in grad_fn's backward moves the dense fp32
        gradient once per round (counted from shape — tier=mesh, so
        telemetry.wan_bytes() stays honest)."""
        if self._mesh is not None:
            self.kv.count_collective(self.total * 4)

    def warmup(self, X, y) -> None:
        """Trace+compile the device programs :meth:`step` will run
        WITHOUT running them: they donate the trainer's state, so they
        are lowered and compiled for these arguments (the executable is
        the one the first call finds) and the state stays untouched —
        lets callers serialize expensive first compiles without holding
        up the FSA barrier (at 59M parameters the forward program is
        about a minute of cold compile on a v5e). What the process has
        built by then it keeps for the life of the job, so the heap is
        settled on the way out (``runtime.settle_heap``): the round
        loop's full collections walk the rounds' garbage, not the
        programs."""
        import jax

        X, y = self._place_batch(X, y)
        args = (self._flat, self._u, self._v, X, y)
        if self._mesh_quant:
            args += (self._mesh_res,)
        fwd = self._fwd_chunks_q if self._mesh_quant else self._fwd_chunks
        fwd.lower(*args).compile()
        for (_lo, _hi, flo, fsize, _cap), up in zip(self._chunk_meta,
                                                   self._uploads):
            # the held upload, put as a round puts it; no program reads
            # this copy, so the first round writes the buffer freely
            up_d = jax.device_put(up)
            self._apply_chunk.lower(self._flat, self._mom, up_d, flo,
                                    fsize).compile()
        runtime.settle_heap()

    # -- one round -------------------------------------------------------

    def _chunk_wire_parts(self, ci: int, arr: np.ndarray):
        """Split chunk ``ci``'s fetched pack into the per-key wire lists
        (keys, values, KEY-relative indices) the store's round takes."""
        sel_lo, sel_hi, _flo, _fsize, _cap = self._chunk_meta[ci]
        kc = sel_hi - sel_lo
        vals = arr[:kc].view(np.float32)
        aidx = arr[kc:]  # int32, as the wire carries them
        keys, vlist, ilist = [], [], []
        for i in self._chunks[ci].items:
            lo = int(self._kofs[i]) - sel_lo
            hi = int(self._kofs[i + 1]) - sel_lo
            keys.append(self.begin_key + i)
            vlist.append(vals[lo:hi])
            ilist.append(aidx[lo:hi] - int(self._offsets[i]))
        return keys, vlist, ilist

    def _chunk_up(self, ci: int, agg: Dict) -> np.ndarray:
        """Chunk ``ci``'s fixed-size upload from its keys' aggregated
        (values, key-relative indices): [vals(cap) bitcast i32,
        idx(cap) chunk-relative]. The ``n`` real entries come first,
        keys in the chunk's order, so their positions ascend strictly
        under ``fsize``; every slot ``s`` left over holds value 0.0 at
        position ``fsize + s``, ascending on and past the chunk's end,
        which ``apply_chunk`` drops.

        What comes back is the ONE buffer the trainer holds for the
        chunk, written in place: each key's values and rebased positions
        in one pass each, straight into their slots, then only what the
        last round left behind (its slots past this round's ``n`` go
        back to pad). A round allocates nothing the size of the upload.

        ``jax.device_put`` returns before the chip has the copy, and the
        CPU backend may alias the numpy memory for the device array's
        life: a buffer must not be written while a program may still
        read its last upload. Hence one buffer a chunk, never one shared
        by two chunks, and in ``step`` a chunk's buffer is next written
        a round later, after that round's ``np.asarray(packs[ci])``: the
        pack comes from the ``fwd_chunks`` that reads the ``flat`` which
        the round's every ``apply_chunk`` wrote, so the apply that read
        this buffer is done. A caller with no such fence between two
        uploads of a chunk copies what it got."""
        _sel_lo, _sel_hi, flo, fsize, cap = self._chunk_meta[ci]
        items = self._chunks[ci].items
        parts = [agg[self.begin_key + i] for i in items]
        n = sum(len(avals) for avals, _aidx in parts)
        if n > cap:
            raise RuntimeError(
                f"aggregated selection ({n}) exceeds chunk upload "
                f"capacity ({cap}) — is the PS tier running an "
                "optimizer? DeviceResidentTrainer requires aggregator "
                "mode")
        up = self._uploads[ci]
        vals, idx = up[:cap].view(np.float32), up[cap:]
        stale = self._filled[ci]
        if stale > n:
            vals[n:stale] = 0.0
            idx[n:stale] = np.arange(fsize + n, fsize + stale,
                                     dtype=np.int32)
        self._filled[ci] = n
        at = 0
        for i, (avals, aidx) in zip(items, parts):
            end = at + len(avals)
            vals[at:end] = avals
            # one pass whatever the positions' type (the frame's int32
            # view, a sharded key's int64): rebased they fit int32
            np.add(aidx, int(self._offsets[i]) - flo, out=idx[at:end],
                   casting="unsafe")
            at = end
        telemetry.counter_inc("trainer.upload_inplace_keys", len(items))
        return up

    def step(self, X, y) -> float:
        """One FSA round: device grad+compress, HiPS aggregate, device
        sparse apply. Returns the loss (device-computed, host float).

        The round runs per chunk: fetch+dispatch every chunk in layer
        order (priority -chunk), then apply each chunk's aggregate as
        it arrives. Chunk flat ranges partition [0, total) and every
        coordinate's arithmetic is the same whatever the chunking, so
        the post-round state does not depend on P3_SLICE_BYTES."""
        import jax

        # who this trainer is on a trace: its worker's node in its
        # overlay (a local store has neither)
        po = getattr(self.kv, "po", None)
        me = po.van.round_args(-1) if po is not None else {}
        with profiler.scope("trainer.step", cat="trainer", **me) as whole:
            X, y = self._place_batch(X, y)
            self._count_mesh_round()
            loss_d, packs = self._run_fwd_chunks(X, y)
            for p in packs:
                if hasattr(p, "copy_to_host_async"):
                    p.copy_to_host_async()
            futs = []
            for ci in range(len(self._chunks)):
                # the round's id is allotted at the dispatch below: the
                # fetch before it carries its chunk alone. The device's
                # part of the round ends inside this fetch; what is left
                # of it after that is the D2H copy
                with profiler.scope("trainer.fetch", cat="trainer",
                                    **dict(me, chunk=ci)):
                    arr = np.asarray(packs[ci])
                with profiler.scope("trainer.pack", cat="trainer",
                                    **dict(me, chunk=ci)) as span:
                    keys, vlist, ilist = self._chunk_wire_parts(ci, arr)
                    # slice_bytes=0: this call IS one chunk — one message
                    # per server, the store must not re-slice it
                    futs.append(self.kv.push_pull_bsc_batch_async(
                        keys, vlist, ilist, priority=-ci, slice_bytes=0))
                    span.set_metadata(round=futs[-1].trace_round)
            if futs:
                whole.set_metadata(round=futs[0].trace_round)
            # loss value-fetch rides behind the dispatches (the wire is
            # already flying when this blocks on the device)
            loss = self._book(np.asarray(loss_d))
            for ci, fut in enumerate(futs):
                args = dict(me, chunk=ci, round=fut.trace_round)
                with profiler.scope("trainer.wait", cat="trainer", **args):
                    agg = fut.results()
                with profiler.scope("trainer.unpack", cat="trainer",
                                    **args):
                    up = self._chunk_up(ci, agg)
                _sel_lo, _sel_hi, flo, fsize, _cap = self._chunk_meta[ci]
                with profiler.scope("trainer.h2d", cat="trainer", **args):
                    up_d = jax.device_put(up)
                with profiler.scope("trainer.apply", cat="trainer",
                                    **args):
                    self._flat, self._mom = self._apply_chunk(
                        self._flat, self._mom, up_d, flo, fsize)
        return loss

    # -- escape hatch ----------------------------------------------------

    @property
    def leaves(self) -> List[np.ndarray]:
        """Materialize current params on host (ONE transfer) — for eval
        or checkpointing, not the training loop."""
        flat = np.asarray(self._flat)
        return [flat[o:o + s].reshape(sh) for o, s, sh in
                zip(self._offsets[:-1], self._sizes, self._shapes)]
