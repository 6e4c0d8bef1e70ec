#!/usr/bin/env python
"""Bi-Sparse HiPS with the DEVICE-RESIDENT trainer (TPU-first flagship).

Same PS semantics as cnn_bsc.py (aggregator tiers, worker-side
optimizer, BSC both directions) but the worker keeps parameters on the
accelerator: per round the host<->device link carries one packed top-k
selection down and the aggregated nonzeros up
(geomx_tpu.trainer_device.DeviceResidentTrainer). On a host whose chip
sits across a network link this is the difference between
transfer-bound and protocol-bound training (see PERF.md).

Run exactly like cnn_bsc.py (scripts/hips_env.sh topology), or
single-process smoke: ``python examples/cnn_bsc_device.py --local``.
"""

import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    logging.basicConfig(level=logging.INFO)
    parser = argparse.ArgumentParser()
    parser.add_argument("-lr", "--learning-rate", type=float, default=0.05)
    parser.add_argument("-mom", "--momentum", type=float, default=0.0)
    parser.add_argument("-bs", "--batch-size", type=int, default=32)
    parser.add_argument("-ds", "--data-slice-idx", type=int, default=0)
    parser.add_argument("-ep", "--epoch", type=int, default=5)
    parser.add_argument("-cr", "--compression-ratio", type=float,
                        default=0.02)
    parser.add_argument("-c", "--cpu", action="store_true")
    parser.add_argument("--local", action="store_true",
                        help="single-process smoke (kv.create('local'))")
    parser.add_argument("--eval-every", type=int, default=5,
                        help="accuracy-eval cadence (tr.leaves pays one "
                             "full-weight device->host transfer)")
    parser.add_argument("--max-iters", type=int, default=0)
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")

    from geomx_tpu.runtime import setup_compile_cache

    setup_compile_cache()
    import jax.numpy as jnp

    import geomx_tpu as gx
    from examples.utils import Measure, build_model_and_step, eval_acc, \
        load_data
    from geomx_tpu.trainer_device import DeviceResidentTrainer

    kv = gx.kv.create("local" if args.local else "dist_sync")
    if getattr(kv, "is_master_worker", False) or args.local:
        # WAN hop sparsified both directions, like cnn_bsc.py:50
        kv.set_gradient_compression(
            {"type": "bsc", "threshold": args.compression_ratio})
    num_all_workers = getattr(kv, "num_all_workers", 1) or 1
    my_rank = getattr(kv, "rank", 0)
    time.sleep(0 if args.local else 1)

    leaves, _treedef, grad_step, eval_step = build_model_and_step(
        args.batch_size)
    if getattr(kv, "is_master_worker", False):
        for idx, leaf in enumerate(leaves):
            kv.init(idx, leaf)
        kv.wait()
        return

    tr = DeviceResidentTrainer(
        leaves, kv, grad_step, threshold=args.compression_ratio,
        learning_rate=args.learning_rate, momentum=args.momentum)

    train_iter, test_iter, _, _ = load_data(
        args.batch_size, num_all_workers, args.data_slice_idx)

    begin_time = time.time()
    global_iters = 1
    measure = Measure(sub_dir=f"cnn_bsc_device_rank{my_rank}")
    print(f"Start training on {num_all_workers} workers, "
          f"my rank is {my_rank}.")
    test_acc = 0.0
    for epoch in range(args.epoch):
        for X, y in train_iter:
            loss = tr.step(jnp.asarray(X), jnp.asarray(y))
            # tr.leaves materializes the full params device->host; keep
            # it OFF the per-round path (the whole point of the
            # device-resident trainer) and eval on a cadence
            if global_iters % args.eval_every == 0:
                test_acc = eval_acc(test_iter, tr.leaves, eval_step)
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
