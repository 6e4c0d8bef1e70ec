#!/usr/bin/env python
"""FP16 low-precision transmission (reference: examples/cnn_fp16.py).

The reference casts the whole gluon net to float16 so pushes/pulls travel
as fp16. TPU-idiomatically we keep f32 params and compute, and cast the
WIRE payloads to fp16: gradients are pushed as float16 and the server
aggregates in f32, storing/serving fp16 — halving WAN traffic with the
same convergence envelope.
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

    kv = gx.kv.create("dist_sync")
    if kv.is_master_worker:
        kv.set_optimizer(gx_opt.Adam(learning_rate=args.learning_rate))
        # weights live on the servers as fp16: keep fp32 masters there
        # (reference: kSetMultiPrecision, kvstore_dist_server.h:324)
        kv.set_multi_precision()
    num_all_workers = kv.num_all_workers
    my_rank = kv.rank
    time.sleep(1)

    leaves, _treedef, grad_step, eval_step = build_model_and_step(
        args.batch_size, compute_dtype=jnp.bfloat16)

    # fp16 on the wire: init and all traffic in float16
    leaves16 = [l.astype(np.float16) for l in leaves]
    for idx, leaf in enumerate(leaves16):
        kv.init(idx, leaf)
        if kv.is_master_worker:
            continue
        kv.pull(idx, out=leaves16[idx])
    kv.wait()
    if kv.is_master_worker:
        return
    leaves = [l.astype(np.float32) for l in leaves16]

    train_iter, test_iter, _, _ = load_data(
        args.batch_size, num_all_workers, args.data_slice_idx,
        split_by_class=args.split_by_class)

    begin_time = time.time()
    global_iters = 1
    measure = Measure(sub_dir=f"cnn_fp16_rank{my_rank}")
    print(f"Start training on {num_all_workers} workers, my rank is {my_rank}.")
    for epoch in range(args.epoch):
        for X, y in train_iter:
            loss, grads = grad_step([jnp.asarray(l) for l in leaves],
                                    jnp.asarray(X), jnp.asarray(y))
            keylist = list(range(len(grads)))
            kv.push(keylist, [np.asarray(g).astype(np.float16)
                              for g in grads])
            kv.pull(keylist, out=leaves16)
            kv.wait()
            leaves = [l.astype(np.float32) for l in leaves16]

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
