#!/usr/bin/env python
"""Expert-parallel MoE transformer training over a dp x ep mesh.

Beyond the reference (SURVEY.md §2.3 — its op set predates MoE): a
decoder-only transformer whose FFNs are top-1 Switch-style MoE blocks
(geomx_tpu.models.moe), expert weights sharded over the "ep" mesh axis,
batch over "dp"; GSPMD inserts the expert-parallel collectives from the
shardings. Includes the load-balancing auxiliary loss.

    python examples/train_moe.py --cpu --ep 2 --experts 4

On CPU set XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--experts", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--ep", type=int, default=1)
    ap.add_argument("--aux-weight", type=float, default=0.01)
    ap.add_argument("-lr", "--learning-rate", type=float, default=3e-4)
    ap.add_argument("--max-iters", type=int, default=20)
    ap.add_argument("-c", "--cpu", action="store_true")
    return ap.parse_args()


def main():
    args = parse_args()
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
        Transformer, transformer_param_sharding)
    from geomx_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices(), ep=args.ep)
    dp = mesh.devices.shape[0]
    print(f"mesh: dp={dp} ep={args.ep} ({len(jax.devices())} x "
          f"{jax.devices()[0].device_kind}), {args.experts} experts")

    model = Transformer(vocab=args.vocab, dim=args.dim, depth=args.depth,
                        heads=args.heads, max_len=args.seq_len,
                        moe_experts=args.experts,
                        compute_dtype=jnp.bfloat16)

    rng = np.random.RandomState(0)
    base = rng.randint(0, args.vocab, (args.batch_size, 1))
    tokens_np = (base + np.arange(args.seq_len)[None, :]) % args.vocab
    tokens = jnp.asarray(tokens_np, jnp.int32)

    with mesh:
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        params = transformer_param_sharding(mesh)(params)
        tokens = jax.device_put(
            tokens, NamedSharding(mesh, P("dp", None)))
        opt = optax.adamw(args.learning_rate)
        opt_state = opt.init(params)

        def loss_fn(p, toks):
            logits, state = model.apply(
                {"params": p}, toks[:, :-1], mutable=["losses"])
            tgt = toks[:, 1:]
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, tgt).mean()
            aux = sum(jnp.asarray(v).sum()
                      for v in jax.tree_util.tree_leaves(
                          state.get("losses", {})))
            return ce + args.aux_weight * aux, (ce, aux)

        @jax.jit
        def step(p, s, toks):
            (loss, (ce, aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p, toks)
            updates, s = opt.update(grads, s, p)
            return optax.apply_updates(p, updates), s, ce, aux

        t0 = time.time()
        for it in range(1, args.max_iters + 1):
            params, opt_state, ce, aux = step(params, opt_state, tokens)
            print(f"[Time {time.time() - t0:.3f}][Iteration {it}] "
                  f"Loss {float(ce):.4f} Aux {float(aux):.4f}",
                  flush=True)


if __name__ == "__main__":
    main()
