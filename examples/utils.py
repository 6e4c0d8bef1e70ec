"""Shared example harness (reference: examples/utils.py).

Provides the data loaders (via geomx_tpu.io), a jitted train/eval step pair
for the demo CNN, flat parameter<->pytree plumbing for the KVStore integer
key space, and the Measure JSON reporter (reference: examples/utils.py:120).
"""

from __future__ import annotations

import json
import os
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from geomx_tpu.io import load_data  # noqa: F401  (re-export)
from geomx_tpu.models import create_cnn


def build_model_and_step(batch_size: int, compute_dtype=jnp.float32,
                         num_classes: int = 10,
                         input_shape=(28, 28, 1), model: str = "cnn"):
    """Returns (param_leaves, treedef, grad_step, eval_step).

    grad_step(leaves, X, y) -> (loss, grad_leaves); mean-normalized grads
    (the reference pushes grad/num_samples, examples/cnn.py:123 — MXNet
    grads are per-batch sums; JAX mean-loss grads are already normalized).

    ``model``: "cnn" (the reference demo net) or any
    ``geomx_tpu.models.get_model`` zoo name ("resnet18", "mobilenet1.0",
    "vgg11", "densenet121", ...). BatchNorm running stats stay
    WORKER-LOCAL (not pushed through the kvstore) — the reference's
    kvstore flow treats BN aux states the same way: only optimizer-
    updated parameters travel.

    Contract note: the zoo-path grad_step/eval_step close over a
    mutable batch_stats box, so unlike the cnn path they are STATEFUL —
    do not wrap them in an outer jax.jit and do not share one instance
    across concurrent workers; call build_model_and_step per worker.
    """
    rng = jax.random.PRNGKey(42)  # same init on every worker process
    if model == "cnn":
        net = create_cnn(num_classes=num_classes,
                         compute_dtype=compute_dtype)
        params = net.init(rng, jnp.zeros((1, *input_shape), jnp.float32))
        leaves, treedef = jax.tree_util.tree_flatten(params)

        def loss_fn(leaf_list, X, y):
            p = jax.tree_util.tree_unflatten(treedef, leaf_list)
            logits = net.apply(p, X)
            one_hot = jax.nn.one_hot(y, num_classes)
            return -jnp.mean(
                jnp.sum(jax.nn.log_softmax(logits) * one_hot, axis=-1))

        @jax.jit
        def grad_step(leaf_list, X, y):
            loss, grads = jax.value_and_grad(loss_fn)(leaf_list, X, y)
            return loss, grads

        @jax.jit
        def eval_step(leaf_list, X, y):
            p = jax.tree_util.tree_unflatten(treedef, leaf_list)
            pred = jnp.argmax(net.apply(p, X), axis=-1)
            return jnp.mean((pred == y).astype(jnp.float32))

    else:
        from geomx_tpu.models import get_model

        # small_images: cifar/mnist-sized stem for the resnet family
        # (forwarded through the zoo factory; other families size by
        # their conv/pool stacks alone)
        extra = {"small_images": True} if model.startswith("resnet") \
            else {}
        net = get_model(model, num_classes=num_classes,
                        compute_dtype=compute_dtype, **extra)
        variables = net.init(rng, jnp.zeros((1, *input_shape), jnp.float32))
        leaves, treedef = jax.tree_util.tree_flatten(variables["params"])
        has_bn = "batch_stats" in variables
        state_box = {"batch_stats": variables.get("batch_stats", {}),
                     "step": 0}

        def loss_fn(leaf_list, bstats, step, X, y):
            p = jax.tree_util.tree_unflatten(treedef, leaf_list)
            vs = {"params": p, **({"batch_stats": bstats} if has_bn
                                  else {})}
            # fresh dropout mask per step: fold the step counter into
            # the key (a closed-over key would bake ONE mask into the
            # jitted trace and train a fixed subnetwork)
            rngs = {"dropout": jax.random.fold_in(
                jax.random.PRNGKey(7), step)}
            if has_bn:
                logits, updates = net.apply(vs, X, train=True,
                                            mutable=["batch_stats"],
                                            rngs=rngs)
                new_bs = updates["batch_stats"]
            else:
                logits = net.apply(vs, X, train=True, rngs=rngs)
                new_bs = bstats
            one_hot = jax.nn.one_hot(y, num_classes)
            loss = -jnp.mean(
                jnp.sum(jax.nn.log_softmax(logits) * one_hot, axis=-1))
            return loss, new_bs

        @jax.jit
        def _grad_step(leaf_list, bstats, step, X, y):
            (loss, new_bs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(leaf_list, bstats, step, X, y)
            return loss, grads, new_bs

        def grad_step(leaf_list, X, y):
            step = state_box["step"]
            state_box["step"] = step + 1
            loss, grads, state_box["batch_stats"] = _grad_step(
                leaf_list, state_box["batch_stats"],
                jnp.asarray(step, jnp.int32), X, y)
            return loss, grads

        @jax.jit
        def _eval_step(leaf_list, bstats, X, y):
            p = jax.tree_util.tree_unflatten(treedef, leaf_list)
            vs = {"params": p, **({"batch_stats": bstats} if has_bn
                                  else {})}
            logits = net.apply(vs, X)
            pred = jnp.argmax(logits, axis=-1)
            return jnp.mean((pred == y).astype(jnp.float32))

        def eval_step(leaf_list, X, y):
            return _eval_step(leaf_list, state_box["batch_stats"], X, y)

    # writable host copies (np.asarray of a jax array is a read-only view)
    return ([np.array(l, copy=True) for l in leaves], treedef, grad_step,
            eval_step)


def build_mesh_ring_step(kv, grad_step):
    """Quantized mesh tier (GEOMX_MESH_CODEC != "none"): wrap the demo
    grad_step so the batch shards over the party mesh's "dp" axis, each
    rank computes LOCAL grads (no XLA-inserted psum), and every leaf is
    party-mean-reduced through the store's quantized ppermute ring
    (``kv.ring_reducer`` — error-feedback residual streams live in the
    store, keyed, so round aborts zero them in one place). Returns a
    drop-in ``(lv, X, y) -> (loss, grads)`` whose outputs are replicated
    and bit-identical on every mesh rank.

    Only valid for STATELESS grad_steps (the "cnn" path of
    build_model_and_step); the zoo path mutates a host-side
    batch_stats box per call and cannot be re-traced under shard_map.
    """
    from jax.sharding import PartitionSpec as P

    mesh = kv.mesh

    def _local(lv, X, y):
        loss, grads = grad_step(lv, X, y)
        return loss[None], [g[None] for g in grads]

    local_step = jax.jit(jax.shard_map(
        _local, mesh=mesh, in_specs=(P(), P("dp"), P("dp")),
        out_specs=(P("dp"), P("dp")), check_vma=False))

    def ring_step(lv, X, y):
        X, y = kv.shard_batch(jnp.asarray(X), jnp.asarray(y))
        losses, grads = local_step([jnp.asarray(l) for l in lv], X, y)
        out = []
        for idx, g in enumerate(grads):
            shape = g.shape[1:]
            n = int(np.prod(shape)) if shape else 1
            red = kv.ring_reducer(idx, n, mean=True)
            out.append(red.reduce(g.reshape(g.shape[0], -1))
                       .reshape(shape))
        kv.record_round_collectives(out, op="ring")
        return jnp.mean(losses), out

    return ring_step


def build_flat_step(leaves: List[np.ndarray], grad_step):
    """Fuse the per-leaf param/grad transfers into ONE array each way.

    Returns ``(flat_grad_step, pack, unpack)`` where
    ``flat_grad_step(flat_params, X, y) -> (loss, flat_grads)`` is jitted
    (split/reshape/concat happen ON DEVICE and fuse away), ``pack`` maps
    a leaf list to one flat fp32 vector and ``unpack`` maps a flat
    vector back to per-key leaves.

    Why: each host->device transfer pays the link latency once. A
    per-leaf device_put of the demo CNN costs 8 transfers each way per
    training round; packed, the whole round is 2, and the DMAs batch.
    (The reference's engine hides this with per-key async ops,
    kvstore_dist.h:567 — in JAX the equivalent is one fused transfer,
    not N async ones.)
    """
    shapes = [l.shape for l in leaves]
    sizes = [int(l.size) for l in leaves]
    bounds = list(np.cumsum(sizes)[:-1])
    dtypes = {np.asarray(l).dtype for l in leaves}
    if len(dtypes) != 1:
        raise ValueError(f"leaves must share one dtype, got {dtypes}")
    dtype = dtypes.pop()

    @jax.jit
    def flat_grad_step(flat, X, y):
        parts = jnp.split(flat, bounds)
        lv = [p.reshape(s) for p, s in zip(parts, shapes)]
        loss, grads = grad_step(lv, X, y)
        return loss, jnp.concatenate([g.reshape(-1) for g in grads])

    def pack(lv: List[np.ndarray]) -> np.ndarray:
        # host-side on purpose: one np.concatenate feeds ONE device_put
        # (jnp/ravel_pytree here would eagerly create per-leaf device
        # arrays, re-paying the per-transfer latency this fn removes)
        return np.concatenate([np.asarray(l, dtype).ravel() for l in lv])

    def unpack(flat: np.ndarray) -> List[np.ndarray]:
        return [p.reshape(s)
                for p, s in zip(np.split(np.asarray(flat), bounds), shapes)]

    return flat_grad_step, pack, unpack


def eval_acc(test_iter, leaves: List[np.ndarray], eval_step) -> float:
    accs = []
    jleaves = [jnp.asarray(l) for l in leaves]
    for X, y in test_iter:
        accs.append(float(eval_step(jleaves, jnp.asarray(X), jnp.asarray(y))))
    return float(np.mean(accs)) if accs else 0.0


class Measure:
    """Per-iteration JSON metrics reporter (reference: utils.py:120)."""

    def __init__(self, log_dir: str = "/tmp/geomx_logs", sub_dir: str = "run"):
        self.begin = time.time()
        self.records = []
        self.log_path = os.path.join(log_dir, sub_dir)
        os.makedirs(self.log_path, exist_ok=True)

    def add(self, iteration: int, epoch: int, accuracy: float,
            num_samples: int, loss: float = 0.0):
        rec = {
            "iteration": iteration,
            "epoch": epoch,
            "time": round(time.time() - self.begin, 4),
            "accuracy": round(accuracy, 4),
            "num_samples": num_samples,
            "loss": round(float(loss), 5),
        }
        self.records.append(rec)
        return rec

    def dump(self, name: str = "measure.json"):
        path = os.path.join(self.log_path, name)
        with open(path, "w") as f:
            json.dump(self.records, f)
        return path
