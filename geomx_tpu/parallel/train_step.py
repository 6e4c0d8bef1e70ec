"""Jitted train steps over the device mesh (tier 0/1) and the hierarchical
trainer that composes them with the inter-DC KVStore (tier 2).

The reference's intra-DC data path (worker Comm reduce + worker<->server
push/pull, kvstore_dist.h:329-478) is HERE, as a single jitted step: the
batch is sharded over "dp", gradients are mean-reduced by XLA-inserted
collectives, and the optimizer update runs on-device. The hierarchical
trainer then periodically exchanges the *aggregated* gradient/weights with
the HiPS global tier through the host KVStore — the only part that
touches the WAN.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class DataParallelTrainer:
    """Pure in-mesh DP: params replicated, batch sharded over "dp"."""

    def __init__(self, model, optimizer: optax.GradientTransformation,
                 mesh: Mesh, example_input: jnp.ndarray,
                 num_classes: int = 10, rng_seed: int = 42):
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        params = model.init(jax.random.PRNGKey(rng_seed), example_input)
        self.repl = NamedSharding(mesh, P())
        self.batch_shard = NamedSharding(mesh, P("dp"))
        self.params = jax.device_put(params, self.repl)
        self.opt_state = jax.device_put(optimizer.init(params), self.repl)
        self.num_classes = num_classes

        def loss_fn(p, X, y):
            logits = model.apply(p, X)
            one_hot = jax.nn.one_hot(y, num_classes)
            return -jnp.mean(
                jnp.sum(jax.nn.log_softmax(logits) * one_hot, axis=-1))

        # donate the incoming params/opt-state: step() rebinds both to
        # the outputs, so XLA may update the old buffers in place
        # instead of holding two copies live across the update
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def train_step(p, opt_state, X, y):
            loss, grads = jax.value_and_grad(loss_fn)(p, X, y)
            updates, opt_state = optimizer.update(grads, opt_state, p)
            p = optax.apply_updates(p, updates)
            return p, opt_state, loss

        @jax.jit
        def grad_step(p, X, y):
            return jax.value_and_grad(loss_fn)(p, X, y)

        # per-rank LOCAL grads (no psum): the quantized-ring mesh path
        # replaces XLA's inserted collective with an explicit one, so it
        # needs each rank's un-reduced contribution, stacked on a
        # leading "dp" axis the ring's shard_map then consumes
        def _local(p, X, y):
            loss, grads = jax.value_and_grad(loss_fn)(p, X, y)
            return (loss[None],
                    jax.tree_util.tree_map(lambda g: g[None], grads))

        self._local_grad_step = jax.jit(jax.shard_map(
            _local, mesh=mesh, in_specs=(P(), P("dp"), P("dp")),
            out_specs=(P("dp"), P("dp")), check_vma=False))

        self._train_step = train_step
        self._grad_step = grad_step

    def shard_batch(self, X, y):
        return (jax.device_put(jnp.asarray(X), self.batch_shard),
                jax.device_put(jnp.asarray(y), self.batch_shard))

    def step(self, X, y) -> float:
        X, y = self.shard_batch(X, y)
        self.params, self.opt_state, loss = self._train_step(
            self.params, self.opt_state, X, y)
        return float(loss)

    def grads(self, X, y):
        """Mesh-aggregated (mean) gradients — tier-1 output for tier-2."""
        X, y = self.shard_batch(X, y)
        return self._grad_step(self.params, X, y)

    def local_grads(self, X, y):
        """Per-rank local mean grads, each leaf stacked ``(P, *shape)``
        over "dp" (NOT reduced — feed these to the quantized ring);
        losses come back ``(P,)``, one per rank."""
        X, y = self.shard_batch(X, y)
        return self._local_grad_step(self.params, X, y)


class HierarchicalTrainer:
    """Tier-1 mesh aggregation + tier-2 HiPS exchange (geo-DP on TPU).

    Replaces the reference worker's per-layer push/pull loop
    (examples/cnn.py:121-124): the mesh IS the data center; the KVStore
    carries only one aggregated gradient per key across the WAN. The
    global server runs the optimizer (FSA semantics) and the fresh
    parameters are installed back onto the mesh.
    """

    def __init__(self, trainer: DataParallelTrainer, kvstore,
                 priority_by_key: bool = True):
        self.t = trainer
        self.kv = kvstore
        self.priority_by_key = priority_by_key
        # mesh-party store (kvstore.mesh_party): the trainer's mesh IS
        # the party — grads() already carries the intra-party psum, so
        # the van round shrinks to the global worker's combined
        # push_pull and the fresh params broadcast back via _install
        # (a replicated device_put, no LAN PS hop)
        self._mesh_store = getattr(kvstore, "mesh", None) is not None \
            and hasattr(kvstore, "record_round_collectives")
        leaves, self.treedef = jax.tree_util.tree_flatten(self.t.params)
        self._shapes = [l.shape for l in leaves]
        self._host = [np.array(l, copy=True) for l in leaves]

    def init_on_kvstore(self) -> None:
        for idx, leaf in enumerate(self._host):
            self.kv.init(idx, leaf)
            if not getattr(self.kv, "is_master_worker", False):
                self.kv.pull(idx, out=self._host[idx])
        self.kv.wait()
        self._install()

    def _install(self) -> None:
        leaves = [jnp.asarray(h) for h in self._host]
        self.t.params = jax.device_put(
            jax.tree_util.tree_unflatten(self.treedef, leaves), self.t.repl)

    def step(self, X, y) -> float:
        if self._mesh_store and \
                getattr(self.kv, "mesh_codec", "none") != "none":
            return self._step_mesh_quant(X, y)
        loss, grads = self.t.grads(X, y)
        glist = jax.tree_util.tree_leaves(grads)
        if self._mesh_store:
            return self._step_mesh(glist, loss)
        for idx, g in enumerate(glist):
            pr = -idx if self.priority_by_key else 0
            self.kv.push(idx, np.asarray(g), priority=pr)
            self.kv.pull(idx, out=self._host[idx], priority=pr)
        self.kv.wait()
        self._install()
        return float(loss)

    def _step_mesh_quant(self, X, y) -> float:
        """Quantized mesh round (GEOMX_MESH_CODEC != "none"): per-rank
        local grads go through one quantized ppermute ring PER KEY
        (``kv.ring_reducer`` — the error-feedback residual streams live
        in the store, keyed, so round aborts and elastic resizes reset
        them in one place) instead of the XLA-inserted fp32 psum. The
        ring output is replicated and bit-identical on every rank; the
        van leg and telemetry accounting are the unchanged
        :meth:`_step_mesh`."""
        losses, grads = self.t.local_grads(X, y)
        glist = []
        for idx, g in enumerate(jax.tree_util.tree_leaves(grads)):
            shape = g.shape[1:]
            n = int(np.prod(shape)) if shape else 1
            red = self.kv.ring_reducer(idx, n, mean=True)
            glist.append(red.reduce(g.reshape(g.shape[0], -1))
                         .reshape(shape))
        return self._step_mesh(glist, jnp.mean(losses))

    def _step_mesh(self, glist, loss) -> float:
        """Mesh-party round: the intra-party aggregation already
        happened inside grads() (the psum XLA inserts for the
        dp-sharded mean loss) — account it under tier=mesh, then only
        the party's global worker puts bytes on the van (one combined
        push_pull round); the result broadcasts back into the mesh as
        a replicated device_put."""
        self.kv.record_round_collectives(glist)
        if self.kv.is_global_worker:
            vals = [np.asarray(g) for g in glist]
            if len(vals) == 1:
                self.kv.push_pull(0, vals[0], self._host[0], priority=0)
            else:
                self.kv.push_pull(list(range(len(vals))), vals,
                                  self._host, priority=0)
            self.kv.wait()
        self._install()
        return float(loss)
