#!/usr/bin/env python
"""Vanilla HiPS training: dist_sync (FSA) / dist_async (MixedSync/DCASGD).

Mirror of the reference entrypoint (reference: examples/cnn.py): same CLI
flags, same roles (master worker sets the optimizer on the global server
and exits after init), same per-iteration accuracy print — the observable
correctness signal. Compute is JAX: a jitted value_and_grad step feeds
kv.push/kv.pull over the HiPS tiers.
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
    # the reference defaults to 0.01 (examples/cnn.py:32) but Adam at 0.01
    # plateaus at chance on this CNN; 0.001 learns to >0.95 within an epoch
    parser.add_argument("-lr", "--learning-rate", type=float, default=0.001)
    parser.add_argument("-bs", "--batch-size", type=int, default=32)
    parser.add_argument("-ds", "--data-slice-idx", type=int, default=0)
    parser.add_argument("-dt", "--data-type", type=str, default="mnist",
                        choices=["mnist", "fashion-mnist", "cifar10"])
    parser.add_argument("-m", "--model", type=str, default="cnn",
                        help="cnn | resnet18 | resnet34 | resnet50 | ...")
    parser.add_argument("-ep", "--epoch", type=int, default=5)
    parser.add_argument("-ms", "--mixed-sync", action="store_true")
    parser.add_argument("-dc", "--dcasgd", action="store_true")
    parser.add_argument("-sc", "--split-by-class", action="store_true")
    parser.add_argument("-c", "--cpu", action="store_true")
    parser.add_argument("--max-iters", type=int, default=0,
                        help="stop after N iterations (0 = full epochs)")
    parser.add_argument("--checkpoint-prefix", type=str, default="",
                        help="save params each epoch; resume from the "
                             "latest epoch if one exists")
    args = parser.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")

    from geomx_tpu.runtime import setup_compile_cache

    setup_compile_cache()

    if args.mixed_sync:
        kv = gx.kv.create("dist_async")
        if kv.is_master_worker:
            kv.set_optimizer(gx_opt.Adam(learning_rate=args.learning_rate))
    elif args.dcasgd:
        kv = gx.kv.create("dist_async")
        if kv.is_master_worker:
            kv.set_optimizer(gx_opt.DCASGD(learning_rate=args.learning_rate))
    else:
        # GEOMX_PARTY_MESH=1 resolves this to the mesh-party tier
        # (kvstore "dist_sync_mesh", docs/mesh-party.md): the launch is
        # unchanged, intra-party aggregation moves into the jitted
        # step's psum, and only this process's van speaks to the party
        # server. The factory does the resolution so scripts/run_*.sh
        # stay identical either way.
        kv = gx.kv.create("dist_sync")
        if kv.is_master_worker:
            kv.set_optimizer(gx_opt.Adam(learning_rate=args.learning_rate))
    num_all_workers = kv.num_all_workers
    my_rank = kv.rank
    time.sleep(1)  # let configuration commands land (reference: cnn.py:86)

    input_shape = (32, 32, 3) if args.data_type == "cifar10" else (28, 28, 1)
    leaves, _treedef, grad_step, eval_step = build_model_and_step(
        args.batch_size, input_shape=input_shape, model=args.model)

    if (getattr(kv, "type", "") == "dist_sync_mesh"
            and getattr(kv, "mesh_codec", "none") != "none"
            and args.model == "cnn"
            and not getattr(kv, "is_master_worker", False)):
        # GEOMX_MESH_CODEC: intra-party gradients ride the quantized
        # ppermute ring instead of the fused psum (the zoo path's
        # stateful grad_step cannot be wrapped — see utils)
        from examples.utils import build_mesh_ring_step

        grad_step = build_mesh_ring_step(kv, grad_step)

    start_epoch = 0
    resume_iters = 0
    if args.checkpoint_prefix:
        from geomx_tpu import checkpoint as gx_ckpt

        latest = gx_ckpt.latest_checkpoint(args.checkpoint_prefix)
        if latest is not None:
            saved, _, meta = gx_ckpt.load_checkpoint(
                args.checkpoint_prefix, latest)
            leaves = [np.asarray(l) for l in saved]
            start_epoch = latest
            resume_iters = int(meta.get("iters", 0))
            print(f"Resumed from {args.checkpoint_prefix}-{latest:04d}.ckpt "
                  f"(epoch {latest}, iter {resume_iters}).")

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
        data_type=args.data_type, split_by_class=args.split_by_class)

    begin_time = time.time()
    global_iters = resume_iters + 1 if args.checkpoint_prefix else 1
    measure = Measure(sub_dir=f"cnn_rank{my_rank}")
    print(f"Start training on {num_all_workers} workers, my rank is {my_rank}.")
    for epoch in range(start_epoch, args.epoch):
        for X, y in train_iter:
            if hasattr(kv, "notify_round"):
                # FaultPlan "crash at_round N" rules key off this
                # counter (chaos matrix worker-kill case)
                kv.notify_round(global_iters)
            loss, grads = grad_step([jnp.asarray(l) for l in leaves],
                                    jnp.asarray(X), jnp.asarray(y))
            # combined push_pull: ONE message per server per round (the
            # ack carries the post-round params — bit-identical to
            # push-then-pull, tests/test_batch_wire.py); falls back to
            # the two-op sequence under P3/TSEngine/local stores
            keylist = list(range(len(grads)))
            if hasattr(kv, "push_pull"):
                kv.push_pull(keylist, [np.asarray(g) for g in grads],
                             out=leaves)
            else:
                kv.push(keylist, [np.asarray(g) for g in grads])
                kv.pull(keylist, out=leaves)
            kv.wait()

            test_acc = eval_acc(test_iter, leaves, eval_step)
            print("[Time %.3f][Epoch %d][Iteration %d] Test Acc %.4f"
                  % (time.time() - begin_time, epoch, global_iters, test_acc))
            measure.add(global_iters, epoch, test_acc, len(X), loss)
            if args.max_iters and global_iters >= args.max_iters:
                measure.dump()
                return
            global_iters += 1
        if args.checkpoint_prefix and my_rank == 0:
            from geomx_tpu import checkpoint as gx_ckpt

            gx_ckpt.save_checkpoint(args.checkpoint_prefix, epoch + 1,
                                    [np.asarray(l) for l in leaves],
                                    metadata={"iters": global_iters - 1})
    measure.dump()


if __name__ == "__main__":
    main()
