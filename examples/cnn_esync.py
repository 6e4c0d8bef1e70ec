#!/usr/bin/env python
"""ESync: heterogeneity-balanced synchronous training (beyond parity).

The reference documents this algorithm but ships no code ("to be
integrated", reference README.md:45; Li et al., IEEE TSC 2020). Each
sync round a worker runs M_i local optimizer steps — assigned by the
state server on the party's rank-0 PS so every worker's reach-server
time balances against the slowest — then joins a synchronous model
average. Fast nodes stop idling at the barrier; no stale gradients are
admitted (geomx_tpu/esync.py).

Run like the other examples — one process per DMLC_ROLE, or --local for
a single process. ``--slowdown S`` injects an artificial per-step sleep
so heterogeneity is observable on a uniform host.
"""

import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import geomx_tpu as gx
from geomx_tpu import optimizer as gx_opt
from examples.utils import build_model_and_step, eval_acc, load_data


def main():
    logging.basicConfig(level=logging.INFO)
    parser = argparse.ArgumentParser()
    parser.add_argument("-lr", "--learning-rate", type=float, default=0.001)
    parser.add_argument("-bs", "--batch-size", type=int, default=32)
    parser.add_argument("-ds", "--data-slice-idx", type=int, default=None)
    parser.add_argument("-r", "--rounds", type=int, default=30,
                        help="sync rounds to run")
    parser.add_argument("--slowdown", type=float, default=0.0,
                        help="artificial seconds of extra compute per "
                             "local step (heterogeneity injection)")
    parser.add_argument("--local", action="store_true")
    parser.add_argument("-c", "--cpu", action="store_true")
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")

    from geomx_tpu.runtime import setup_compile_cache

    setup_compile_cache()
    import jax.numpy as jnp

    from geomx_tpu.esync import ESyncTrainer

    kv = gx.kv.create("local" if args.local else "dist_sync")
    my_rank = getattr(kv, "rank", 0)
    time.sleep(0 if args.local else 1)

    leaves, _td, grad_step, eval_step = build_model_and_step(
        args.batch_size)

    if getattr(kv, "is_master_worker", False):
        for idx, leaf in enumerate(leaves):
            kv.init(idx, leaf)
        kv.wait()
        return

    def grad_fn(leaf_list, X, y):
        if args.slowdown:
            time.sleep(args.slowdown)
        loss, grads = grad_step(leaf_list, X, y)
        return float(loss), [np.asarray(g) for g in grads]

    opt = gx_opt.Adam(learning_rate=args.learning_rate)
    tr = ESyncTrainer(leaves, kv, grad_fn, opt)

    slice_idx = args.data_slice_idx if args.data_slice_idx is not None \
        else my_rank
    nslices = max(getattr(kv, "num_all_workers", 1), 1)
    train_iter, test_iter, _, _ = load_data(args.batch_size, nslices,
                                            slice_idx)
    import itertools

    batches = [(jnp.asarray(X), jnp.asarray(y))
               for X, y in itertools.islice(train_iter, 8)]
    for r in range(args.rounds):
        loss = tr.round(batches)
        if r % 5 == 0 or r == args.rounds - 1:
            print(f"[esync rank {my_rank}] round {r} steps={tr.steps} "
                  f"local_steps_total={tr.local_steps_run} "
                  f"loss={loss:.4f}", flush=True)
    acc = eval_acc(test_iter, tr.leaves, eval_step)
    print(f"[esync rank {my_rank}] final acc={acc:.4f} "
          f"local_steps_total={tr.local_steps_run}", flush=True)


if __name__ == "__main__":
    main()
