#!/usr/bin/env python
"""Hierarchical Frequency Aggregation (reference: examples/cnn_hfa.py).

Each worker runs a LOCAL optimizer every step; every K1 steps it pushes
its weights divided by the local worker count (so the party server's sum
is the party average) and pulls the synchronized weights back. The party
server syncs with the global tier only every K2 rounds, exchanging
milestone deltas (server-side logic; enable with MXNET_KVSTORE_USE_HFA=1,
MXNET_KVSTORE_HFA_K1, MXNET_KVSTORE_HFA_K2 — reference:
kvstore_dist_server.h:184-187, 1327-1346).
"""

import argparse
import logging
import os
import sys
import time

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import geomx_tpu as gx
from geomx_tpu import optimizer as gx_opt
from examples.utils import Measure, build_model_and_step, eval_acc, load_data


def main():
    logging.basicConfig(level=logging.INFO)
    parser = argparse.ArgumentParser()
    parser.add_argument("-lr", "--learning-rate", type=float, default=0.001)
    parser.add_argument("-bs", "--batch-size", type=int, default=32)
    parser.add_argument("-ds", "--data-slice-idx", type=int, default=0)
    parser.add_argument("-ep", "--epoch", type=int, default=5)
    parser.add_argument("-sc", "--split-by-class", action="store_true")
    parser.add_argument("-c", "--cpu", action="store_true")
    parser.add_argument("--max-iters", type=int, default=0)
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")

    from geomx_tpu.runtime import setup_compile_cache

    setup_compile_cache()

    period_k1 = int(os.getenv("MXNET_KVSTORE_HFA_K1", 2))

    kv = gx.kv.create("dist_sync")
    num_all_workers = kv.num_all_workers
    num_local_workers = kv.num_workers
    my_rank = kv.rank
    time.sleep(1)

    leaves, _treedef, grad_step, eval_step = build_model_and_step(
        args.batch_size)
    local_opt = gx_opt.Adam(learning_rate=args.learning_rate)

    for idx, leaf in enumerate(leaves):
        kv.init(idx, leaf)
        if kv.is_master_worker:
            continue
        kv.pull(idx, out=leaves[idx])
    kv.wait()
    if kv.is_master_worker:
        return

    train_iter, test_iter, _, _ = load_data(
        args.batch_size, num_all_workers, args.data_slice_idx,
        split_by_class=args.split_by_class)

    begin_time = time.time()
    global_iters = 1
    measure = Measure(sub_dir=f"cnn_hfa_rank{my_rank}")
    print(f"Start training on {num_all_workers} workers, my rank is {my_rank}.")
    for epoch in range(args.epoch):
        for X, y in train_iter:
            loss, grads = grad_step([jnp.asarray(l) for l in leaves],
                                    jnp.asarray(X), jnp.asarray(y))
            # local step every iteration (reference: trainer.step)
            for idx, g in enumerate(grads):
                leaves[idx] = np.asarray(
                    local_opt.update(idx, leaves[idx], np.asarray(g))
                ).reshape(leaves[idx].shape)

            if global_iters % period_k1 == 0:
                # HFA sync: push weights/num_local_workers, pull party avg
                # (reference: cnn_hfa.py:120-123)
                for idx in range(len(leaves)):
                    kv.push(idx, leaves[idx] / num_local_workers,
                            priority=-idx)
                    kv.pull(idx, out=leaves[idx], priority=-idx)
                kv.wait()

                test_acc = eval_acc(test_iter, leaves, eval_step)
                print("[Time %.3f][Epoch %d][Iteration %d] Test Acc %.4f"
                      % (time.time() - begin_time, epoch, global_iters,
                         test_acc))
                measure.add(global_iters, epoch, test_acc, len(X), loss)
            if args.max_iters and global_iters >= args.max_iters:
                measure.dump()
                return
            global_iters += 1
    measure.dump()


if __name__ == "__main__":
    main()
