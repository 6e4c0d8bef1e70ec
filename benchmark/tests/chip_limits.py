"""Read, on the chip and in one process, the two numbers every limit of
``correct`` part (a) is set from: the largest error sound runs of the
program give over a dozen seeds, and the smallest the control gives.

    python -m benchmark.tests.chip_limits --config gpt2-small \
        --seeds 12 --control-seeds 3

Not run by the benchmark's own runs; the result goes to
``chiprun_out/limits_<config>.json`` and into PERF.md section 2.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gpt2-small")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--control-dtype", default="float8_e4m3fn")
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--first-seed", type=int, default=2147483700)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark import correct, manifest
    from benchmark.data import pattern
    from geomx_tpu.runtime import require_tpu, setup_compile_cache

    stamp = require_tpu()
    setup_compile_cache()
    try:
        cfg = manifest.load_config_file(args.config)
    except manifest.ManifestError:
        # a configuration whose cell is not in the manifest yet
        with open(os.path.join(manifest.BENCH_DIR, "configs",
                               args.config + ".json")) as f:
            cfg = json.load(f)
    ref = manifest.family_module("references", cfg["family"])
    mdl = manifest.family_module("models", cfg["family"])
    names, grad_step = mdl.build(cfg, args.seq_len)
    ref_step = correct.reference_step(ref, cfg)
    control_step = correct.reference_step(ref, cfg, args.control_dtype)
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        params = ref.init_params(cfg, seed)
        toks = jnp.asarray(pattern.batch(
            np.random.default_rng(seed), args.sequences, args.seq_len + 1,
            cfg["vocab_size"]))
        row = {"seed": seed, "program": correct.reference_errors(
            ref_step, params, names, grad_step, toks)}
        if i < args.control_seeds:
            row["control"] = correct.control_errors(
                ref_step, control_step, params, names, toks)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        rows.append(row)
    prog = [r["program"]["grad_rel_l2"] for r in rows]
    ctrl = [r["control"]["grad_rel_l2"] for r in rows if "control" in r]
    out = {"config": args.config, "device": stamp,
           "control_dtype": args.control_dtype,
           "program_grad_rel_l2_max": max(prog),
           "program_grad_rel_l2_min": min(prog),
           "control_grad_rel_l2_min": min(ctrl) if ctrl else None,
           "program_loss_rel_err_max": max(
               r["program"]["loss_rel_err"] for r in rows),
           "control_loss_rel_err_min": min(
               (r["control"]["loss_rel_err"] for r in rows
                if "control" in r), default=None),
           "rows": rows}
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/limits_{args.config}.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
