#!/usr/bin/env python
"""Geo-distributed transformer through HiPS + Bi-Sparse, device-resident.

The round-4 flagship config: the 59M-param decoder-only transformer
(the bench model) trains through ``DeviceResidentTrainer`` — parameters
never leave the chip; the host<->device link and the LAN hop carry only
the per-tensor BSC top-k selection down and the aggregated nonzeros up
(the store's one sparse round, push_pull_bsc_batch_async: a combined
element-sparse message per server on KVStoreDist, the selection itself
on the --local store).

Reference lineage: examples/cnn_bsc.py's aggregator-PS + worker-side
optimizer semantics (reference: examples/cnn_bsc.py:37-60), applied to
the model family the reference never had. Run it like the other
examples — one process per DMLC_ROLE, or --local for single-process:

  python examples/transformer_bsc_device.py --local --cpu --max-iters 20

Synthetic LM task: next token = (3*t + 7) mod vocab, a deterministic
pattern every worker slices differently, so the loss curve is a real
learning signal (random tokens would pin loss at log(vocab))."""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def synth_batch(rng, batch, seq_len, vocab):
    """Deterministic-pattern LM batch: x[t+1] = (3*x[t] + 7) % vocab."""
    import numpy as np

    start = rng.integers(0, vocab, size=(batch, 1))
    toks = [start]
    for _ in range(seq_len - 1):
        toks.append((3 * toks[-1] + 7) % vocab)
    return np.concatenate(toks, axis=1).astype(np.int32)


def build_transformer_grad_step(dim, depth, heads, vocab, seq_len,
                                compute_dtype=None):
    """(leaves, grad_step) with the leaf-list contract grad_step(leaves,
    tokens, None) -> (loss, grad_leaves) the trainers expect."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from geomx_tpu.models.transformer import Transformer

    model = Transformer(vocab=vocab, dim=dim, depth=depth, heads=heads,
                        max_len=seq_len,
                        compute_dtype=compute_dtype or jnp.bfloat16)
    rng = jax.random.PRNGKey(42)  # same init on every worker
    params = model.init(rng, jnp.zeros((1, seq_len), jnp.int32))
    leaves, treedef = jax.tree_util.tree_flatten(params)

    def loss_fn(leaf_list, toks):
        p = jax.tree_util.tree_unflatten(treedef, leaf_list)
        logits = model.apply(p, toks[:, :-1])
        tgt = toks[:, 1:]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(
            jnp.take_along_axis(logp, tgt[..., None], axis=-1))

    def grad_step(leaf_list, toks, _y):
        return jax.value_and_grad(loss_fn)(leaf_list, toks)

    return [np.array(l, copy=True) for l in leaves], grad_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("-bs", "--batch-size", type=int, default=8)
    ap.add_argument("-lr", "--learning-rate", type=float, default=0.05)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("-cr", "--compression-ratio", type=float, default=0.01,
                    help="BSC threshold: per-tensor top-k keeps this "
                         "fraction of coordinates")
    ap.add_argument("-ds", "--data-slice-idx", type=int, default=None,
                    help="worker slice id (set by the launch scripts); "
                         "seeds this worker's disjoint data stream; "
                         "defaults to the kv rank when not given")
    ap.add_argument("--max-iters", type=int, default=50)
    ap.add_argument("--local", action="store_true",
                    help="single-process local kvstore (no topology)")
    ap.add_argument("-c", "--cpu", action="store_true")
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")

    from geomx_tpu.runtime import setup_compile_cache

    setup_compile_cache()
    import numpy as np

    import geomx_tpu as gx
    from geomx_tpu.trainer_device import DeviceResidentTrainer

    kv = gx.kv.create("local" if args.local else "dist_sync")
    num_all_workers = getattr(kv, "num_all_workers", 1) or 1
    my_rank = getattr(kv, "rank", 0)
    time.sleep(0 if args.local else 1)

    leaves, grad_step = build_transformer_grad_step(
        args.dim, args.depth, args.heads, args.vocab, args.seq_len)
    n_params = sum(l.size for l in leaves)

    if getattr(kv, "is_master_worker", False):
        for idx, leaf in enumerate(leaves):
            kv.init(idx, leaf)
        kv.wait()
        return

    tr = DeviceResidentTrainer(
        leaves, kv, grad_step, threshold=args.compression_ratio,
        learning_rate=args.learning_rate, momentum=args.momentum)
    print(f"[worker {my_rank}] {n_params / 1e6:.1f}M params, "
          f"per-round selection {tr.k} of {tr.total} "
          f"({100.0 * tr.k / tr.total:.2f}%)", flush=True)

    slice_idx = (my_rank if args.data_slice_idx is None
                 else args.data_slice_idx)
    rng = np.random.default_rng(1234 + slice_idx)  # disjoint data slices
    import jax.numpy as jnp

    begin = time.time()
    for it in range(1, args.max_iters + 1):
        toks = jnp.asarray(synth_batch(rng, args.batch_size,
                                       args.seq_len, args.vocab))
        loss = tr.step(toks, None)
        tokens_s = (it * args.batch_size * args.seq_len * num_all_workers
                    / (time.time() - begin))
        print(f"[Time {time.time() - begin:.3f}][Iteration {it}] "
              f"Loss {loss:.4f} ({tokens_s:.0f} tok/s)", flush=True)


if __name__ == "__main__":
    main()
