#!/usr/bin/env python
"""Bi-Sparse Compression (reference: examples/cnn_bsc.py).

BSC mode = gradient-aggregation-only: the global server holds the summed
gradient (no server optimizer), the WAN hop is sparsified both directions
(push: momentum-corrected top-k; pull: non-zero filter x num parties), and
every worker applies the optimizer LOCALLY on the pulled global gradient
(reference: Trainer(update_on_kvstore=False) + pull into param.grad(),
examples/cnn_bsc.py:77-121).
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
    # reference default: cnn_bsc.py:33 uses lr 0.01 (10x the vanilla
    # example's 0.001 — sparse top-k gradients need the hotter rate)
    parser.add_argument("-lr", "--learning-rate", type=float, default=0.01)
    parser.add_argument("-bs", "--batch-size", type=int, default=32)
    parser.add_argument("-ds", "--data-slice-idx", type=int, default=0)
    parser.add_argument("-ep", "--epoch", type=int, default=5)
    parser.add_argument("-cr", "--compression-ratio", type=float, default=0.01)
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

    kv = gx.kv.create("dist_sync")
    if kv.is_master_worker:
        kv.set_gradient_compression(
            {"type": "bsc", "threshold": args.compression_ratio})
    num_all_workers = kv.num_all_workers
    my_rank = kv.rank
    time.sleep(1)

    leaves, _treedef, grad_step, eval_step = build_model_and_step(
        args.batch_size)
    # local optimizer per worker (reference: Trainer update_on_kvstore=False)
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
    measure = Measure(sub_dir=f"cnn_bsc_rank{my_rank}")
    grad_bufs = [np.zeros_like(l) for l in leaves]
    print(f"Start training on {num_all_workers} workers, my rank is {my_rank}.")
    for epoch in range(args.epoch):
        for X, y in train_iter:
            loss, grads = grad_step([jnp.asarray(l) for l in leaves],
                                    jnp.asarray(X), jnp.asarray(y))
            # one batched message per server each way; the pull-back
            # is the globally-aggregated (sparsified) gradient
            keylist = list(range(len(grads)))
            kv.push(keylist, [np.asarray(g) for g in grads])
            kv.pull(keylist, out=grad_bufs)
            kv.wait()
            for idx in range(len(leaves)):
                leaves[idx] = np.asarray(
                    local_opt.update(idx, leaves[idx], grad_bufs[idx])
                ).reshape(leaves[idx].shape)

            test_acc = eval_acc(test_iter, leaves, eval_step)
            print("[Time %.3f][Epoch %d][Iteration %d] Test Acc %.4f"
                  % (time.time() - begin_time, epoch, global_iters, test_acc))
            measure.add(global_iters, epoch, test_acc, len(X), loss)
            if args.max_iters and global_iters >= args.max_iters:
                measure.dump()
                return
            global_iters += 1
    measure.dump()


if __name__ == "__main__":
    main()
