#!/usr/bin/env python
"""Long-context transformer training over a dp x tp x sp device mesh.

The TPU-first flagship beyond the reference's CNN-era model layer
(SURVEY.md §5.7 — the reference has no attention model at all): batch
shards over "dp", sequence over "sp" (ring attention via
shard_map+ppermute), attention heads and MLP hidden over "tp"
(Megatron-style parameter shardings; GSPMD inserts the collectives).

Single process, all local devices. Try it without hardware:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
  python examples/train_transformer.py --tp 2 --sp 2 --max-iters 10

For geo-distributed training, wrap the aggregated gradients with a
``dist_sync`` KVStore exactly as examples/cnn.py does (the mesh is the
data center; see geomx_tpu.parallel.HierarchicalTrainer).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--sp", type=int, default=1)
    ap.add_argument("-lr", "--learning-rate", type=float, default=3e-4)
    ap.add_argument("--max-iters", type=int, default=20)
    ap.add_argument("--ckpt-dir", type=str, default="",
                    help="sharded-checkpoint dir; resumes from the "
                         "latest step when one exists")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation microbatches per step")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize transformer blocks")
    ap.add_argument("-c", "--cpu", action="store_true")
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from geomx_tpu.runtime import setup_compile_cache

    setup_compile_cache()
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from geomx_tpu.models.transformer import (
        Transformer, make_attention, transformer_param_sharding)
    from geomx_tpu.parallel.mesh import make_mesh
    from geomx_tpu.parallel.ring_attention import make_ring_attention

    mesh = make_mesh(jax.devices(), tp=args.tp, sp=args.sp)
    dp = mesh.devices.shape[0]
    print(f"mesh: dp={dp} tp={args.tp} sp={args.sp} "
          f"({len(jax.devices())} x {jax.devices()[0].device_kind})")

    # sp>1: ring attention (sequence sharded over the mesh); otherwise the
    # per-device pick — Pallas flash kernels on TPU (shard_mapped over
    # dp/tp when the mesh is multi-device), XLA dense elsewhere
    attn = (make_ring_attention(mesh, causal=True) if args.sp > 1
            else make_attention("auto", mesh=mesh))
    model = Transformer(vocab=args.vocab, dim=args.dim, depth=args.depth,
                        heads=args.heads, max_len=args.seq_len,
                        attn_fn=attn, remat=args.remat,
                        compute_dtype=jnp.bfloat16)

    rng = np.random.RandomState(0)
    # synthetic copy-task-ish stream: next token = current + 1 mod vocab,
    # learnable so the loss visibly drops
    base = rng.randint(0, args.vocab, (args.batch_size, 1))
    tokens_np = (base + np.arange(args.seq_len)[None, :]) % args.vocab
    tokens = jnp.asarray(tokens_np, jnp.int32)

    with mesh:
        # init with the FULL batch: ring attention runs under shard_map,
        # whose specs require every axis divisible by its mesh axis
        params = model.init(jax.random.PRNGKey(0), tokens)
        params = transformer_param_sharding(mesh)(params)
        opt = optax.adamw(args.learning_rate)
        opt_state = opt.init(params)
        tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", "sp")))

        def loss_fn(p, toks):
            logits = model.apply(p, toks)
            tgt = jnp.roll(toks, -1, axis=1)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tgt).mean()

        grad_fn = lambda p, toks: jax.value_and_grad(  # noqa: E731
            loss_fn)(p, toks)
        if args.microbatches != 1:
            from geomx_tpu.parallel.grad_accum import accumulate_gradients

            grad_fn = accumulate_gradients(grad_fn, args.microbatches)

        @jax.jit
        def step(p, s, toks):
            loss, grads = grad_fn(p, toks)
            updates, s = opt.update(grads, s, p)
            return optax.apply_updates(p, updates), s, loss

        start_it = 1
        if args.ckpt_dir:
            from geomx_tpu.checkpoint_sharded import (
                latest_step, restore_sharded, save_sharded)

            last = latest_step(args.ckpt_dir)
            if last is not None:
                state = restore_sharded(
                    args.ckpt_dir, last,
                    {"params": params, "opt_state": opt_state})
                params, opt_state = state["params"], state["opt_state"]
                start_it = last + 1
                print(f"resumed from step {last}", flush=True)

        t0 = time.time()
        for it in range(start_it, args.max_iters + 1):
            params, opt_state, loss = step(params, opt_state, tokens)
            print(f"[Time {time.time() - t0:.3f}][Iteration {it}] "
                  f"Loss {float(loss):.4f}", flush=True)
            if args.ckpt_dir and it % args.ckpt_every == 0:
                save_sharded(args.ckpt_dir, it,
                             {"params": params, "opt_state": opt_state})
                print(f"checkpointed step {it}", flush=True)


if __name__ == "__main__":
    main()
