"""The benchmark's one command.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It holds all the cell's chips itself, never falls back to
the CPU, keeps the compile cache at ``<checkout>/.jax_cache``, makes the
weights on the device from ``--seed``, checks the system against the
plain reference, starts a live ``simulate.InProcessHiPS`` topology,
warms the cell's own programs (one worker at a time, so the second loads
from the cache), runs the untimed rounds (two, unless the cell says: the
first is checked in its select, aggregate and apply, and is slower and
lighter on the WAN than those that follow), measures for ``--seconds``, checks the outcome, and
prints ONE JSON object as the last line of its standard output.

The timed window starts at a round boundary and ends at the first round
boundary past ``--seconds``; rates divide by the time between those two
boundaries. Rounds are the trainer's rounds, and the workers also meet
at a boundary of the harness between rounds (that is where the clock is
read and the run told to stop), so a worker's next round starts when
every worker's last one has ended, and a round's time runs from one
boundary to the next: it is the slowest worker's.

``--controls 1`` also prints what the controls of ``correct`` part (b)
read in this run (the next precision down, in numpy); the driver never
passes it.

``--rehearse`` is the labelled dry run for the CPU sandbox (tiny widths
from the configuration's and the cell's ``rehearsal`` groups): it marks
its last line ``"rehearsal": true`` and exits 10, never 0. It is no chip
result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # before any heavy import: setup_s

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402

from benchmark import correct, manifest, readers, trace_reduce  # noqa: E402

DEADLINE_S = 1150           # inside the 1200 s a compiling run may take
KV_TIMEOUTS = {"barrier_timeout_s": 600.0, "op_timeout_s": 600.0}
WORKERS_S = 1000.0
REHEARSAL_EXIT = 10
MAX_TRACE_ROUNDS = 3        # rounds of plain step() under the profiler
WAN_ROUNDS = 3              # wan_mb_per_round: the window's first rounds
UNTIMED_ROUNDS = 2          # before the window, unless the cell says


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Window:
    """The harness's boundary between rounds. Every worker thread calls
    :meth:`meet` after each round; the barrier's action runs in one of
    them while the others wait: it reads the clock, samples the
    program's counters, starts and stops the profiler, and says what the
    next round is: ``step``, ``step_timed`` or ``stop``."""

    def __init__(self, n_workers: int, seconds: float, trace: bool,
                 trace_dir: str, untimed: int):
        from geomx_tpu import telemetry

        self._telemetry = telemetry
        self.seconds, self.trace, self.trace_dir = seconds, trace, trace_dir
        self.warm = threading.Barrier(n_workers)
        self.barrier = threading.Barrier(n_workers, action=self._boundary)
        # rounds of the window that have ended; below 0 while the untimed
        # rounds run: ``untimed`` plain step(), and before a traced window
        # one step_timed() as well, so that nothing compiles in the window
        self.done = -untimed - (1 if trace else 0)
        self.mode = "step"
        # round i of the window runs from starts[i], when boundary i let
        # the workers go, to ends[i], when the last of them was back
        self.starts, self.ends = [], []
        self.snaps = []             # telemetry snapshot at every boundary
        self.traced = None          # (start, end, rounds) of the traced part
        self.trace_write_s = 0.0

    def meet(self) -> str:
        self.barrier.wait(WORKERS_S)
        return self.mode

    def abort(self) -> None:
        self.warm.abort()
        self.barrier.abort()

    def _boundary(self) -> None:
        import jax

        now = time.perf_counter()
        self.done += 1
        if self.done < 0:
            if self.trace and self.done == -1:
                self.mode = "step_timed"
            return
        self.snaps.append(self._telemetry.snapshot())
        if self.done == 0:          # the untimed rounds have ended
            self.mode = "step"
            if self.trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
        else:
            self.ends.append(now)
            if self.trace and self.traced is None:
                # the profiler runs over the first half of the window,
                # to a round boundary, for MAX_TRACE_ROUNDS at most;
                # step_timed() takes the rest, one round at least
                if (now - self.starts[0] >= self.seconds / 2
                        or self.done == MAX_TRACE_ROUNDS):
                    jax.profiler.stop_trace()
                    self.traced = (self.starts[0], now, self.done)
                    self.trace_write_s = time.perf_counter() - now
                    self.mode = "step_timed"
            elif now - self.starts[0] >= self.seconds:
                self.mode = "stop"
        self.starts.append(time.perf_counter())


def _memory_peak() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


RECORDED = ("push_pull_bsc_batch", "push_pull_bsc_batch_async")


def _recording(kv, log: dict) -> None:
    """Record one round at the worker's kvstore: what the trainer handed
    to the combined sparse round (blocking or chunked-async, whichever
    its configuration takes) and what came back, key by key. Undone by
    :func:`_stop_recording`."""
    log["pushed"], log["applied"] = {}, {}

    def copy(keys, values_list, indices_list):
        for k, v, i in zip(keys, values_list, indices_list):
            log["pushed"][k] = (np.array(v, np.float32),
                                np.array(i, np.int64))

    def keep(agg, keys):
        for k in keys:
            log["applied"][k] = (np.array(agg[k][0], np.float32),
                                 np.array(agg[k][1], np.int64))
        return agg

    blocking, chunked = (getattr(kv, name) for name in RECORDED)

    def push_pull_bsc_batch(keys, values_list, indices_list, **kw):
        copy(keys, values_list, indices_list)
        join = blocking(keys, values_list, indices_list, **kw)
        return lambda: keep(join(), keys)

    def push_pull_bsc_batch_async(keys, values_list, indices_list, **kw):
        copy(keys, values_list, indices_list)
        fut = chunked(keys, values_list, indices_list, **kw)
        results = fut.results
        fut.results = lambda *a, **k: keep(results(*a, **k), keys)
        return fut

    kv.push_pull_bsc_batch = push_pull_bsc_batch
    kv.push_pull_bsc_batch_async = push_pull_bsc_batch_async


def _stop_recording(kv) -> None:
    for name in RECORDED:
        delattr(kv, name)


def run(args) -> int:
    man = manifest.load()
    cell = manifest.load_cell(args.workload, man)
    spec, cfg, entry = cell["spec"], dict(cell["config"]), cell["entry"]
    if args.rehearse:
        say("DRY RUN on whatever backend jax picked, tiny widths: NOT a "
            "chip result")
        cfg.update(cfg.get("rehearsal", {}))
        spec = dict(spec, **spec.get("rehearsal", {}))

    import jax
    import jax.numpy as jnp

    from geomx_tpu import telemetry
    from geomx_tpu.runtime import (CompileCounter, device_stamp,
                                   require_tpu, setup_compile_cache)
    from geomx_tpu.simulate import InProcessHiPS
    from geomx_tpu.trainer_device import DeviceResidentTrainer

    stamp = device_stamp() if args.rehearse else require_tpu()
    if stamp["count"] < entry["chips"]:
        raise RuntimeError(f"cell {args.workload} needs {entry['chips']} "
                           f"chip(s), jax reports {stamp['count']}")
    peaks = None if args.rehearse else manifest.peaks_for(stamp["kind"])
    say(f"{args.workload} seed {args.seed} on {stamp}; compile cache "
        f"{setup_compile_cache()}")
    compiles = CompileCounter()
    telemetry.enable(True)
    family = cfg["family"]
    ref = manifest.family_module("references", family)
    mdl = manifest.family_module("models", family)
    seq, bpw = spec["seq_len"], spec["batch_per_worker"]
    # limits read from runs: the configuration's, then the cell's
    limits = dict(cfg.get("limits", {}), **spec.get("limits", {}))
    checks = {}

    # -- weights from the seed, on the device, one jitted call ----------
    t = time.perf_counter()
    params = ref.init_params(cfg, args.seed)
    names, grad_step = mdl.build(cfg, seq)
    device_leaves = mdl.leaves_from(params, names)
    say(f"{sum(x.size for x in device_leaves) / 1e6:.1f}M parameters in "
        f"{len(names)} keys made on the device "
        f"({time.perf_counter() - t:.1f} s)")

    # -- correct (a): the system against the plain reference ------------
    t = time.perf_counter()
    gen = manifest.resolve(f"data.{spec['data']}:batch")
    toks = jnp.asarray(gen(np.random.default_rng(args.seed), 2, seq + 1,
                           cfg["vocab_size"]))
    ref_step = correct.reference_step(ref, cfg)
    errs = correct.reference_errors(ref_step, params, names, grad_step, toks)
    checks["reference"] = (errs["grad_rel_l2"] <= limits["grad_rel_l2"]
                           and np.isfinite(errs["loss"]))
    say(f"correct (a) {family} vs float32 reference, 2 x {seq} tokens: "
        f"grad_rel_l2 {errs['grad_rel_l2']:.6g} (limit "
        f"{limits['grad_rel_l2']}); information, no limit (it does not "
        f"separate the control): loss_rel_err {errs['loss_rel_err']:.6g}, "
        f"loss {errs['loss']:.6f} vs {errs['loss_ref']:.6f} -> "
        f"{'ok' if checks['reference'] else 'FAIL'} "
        f"({time.perf_counter() - t:.1f} s)")

    def batches_of(w):
        rng = np.random.default_rng([args.seed, w])
        return [gen(rng, bpw, seq + 1, cfg["vocab_size"])
                for _ in range(spec["batches"])]

    # for (b): the reference's gradient of worker 0's first batch, to
    # hold that trainer's accumulator against; in shards of the shape
    # the reference is compiled for
    t = time.perf_counter()
    rows = len(toks) if bpw % len(toks) == 0 else 1
    grad0 = correct.batch_gradient(ref_step, params, names,
                                   batches_of(0)[0], rows)
    line = (f"reference gradient of worker 0's first batch, {bpw} x {seq} "
            f"tokens in shards of {rows}")
    if args.controls:
        control = correct.batch_gradient(
            correct.reference_step(ref, cfg, cfg["control_dtype"]), params,
            names, batches_of(0)[0], rows)
        line += (f"; CONTROL {cfg['control_dtype']} operands, grad_rel_l2 "
                 f"{correct.rel_l2(control, grad0):.6g}")
        del control
    say(f"{line} ({time.perf_counter() - t:.1f} s)")

    # the trainer wants host leaves (it pushes them through the kvstore)
    leaves0 = [np.array(x, copy=True) for x in device_leaves]
    flat0 = np.concatenate([l.ravel() for l in leaves0])
    del params, device_leaves, toks

    # -- the topology ----------------------------------------------------
    t = time.perf_counter()
    extra = dict(KV_TIMEOUTS, **spec.get("extra_cfg", {}))
    if spec.get("shape_plan"):
        extra["shape_plan"] = "@" + os.path.join(
            manifest.BENCH_DIR, "shapes", spec["shape_plan"] + ".json")
    topo = InProcessHiPS(
        num_parties=spec["num_parties"],
        workers_per_party=spec["workers_per_party"],
        party_mesh_size=spec["party_mesh_size"], extra_cfg=extra,
        **spec.get("topology", {})).start()
    mesh_party = spec["party_mesh_size"] > 0
    n = len(topo.workers)
    say(f"topology up: {n} trainers, van "
        f"{'+'.join(topo.van_backends())} ({time.perf_counter() - t:.1f} s)")

    trace_dir = os.path.join(manifest.ROOT, "benchmark_out", "trace",
                             f"{args.workload}-{args.seed}")
    win = Window(n, args.seconds, bool(args.trace), trace_dir,
                 spec.get("untimed_rounds", UNTIMED_ROUNDS))
    compile_lock = threading.Lock()
    res = [None] * n

    def master_init(kv):
        if spec.get("server_compression"):
            # the party->global hop, as examples/cnn_bsc_device.py sets it
            kv.set_gradient_compression(dict(spec["server_compression"]))
        for i, leaf in enumerate(leaves0):
            kv.init(i, leaf)
        kv.wait()

    def worker(kv):
        try:
            _worker(kv)
        except BaseException:
            win.abort()     # release the peers now
            raise

    def _worker(kv):
        w = topo.workers.index(kv)
        info = {"losses": [], "timed": []}
        res[w] = info
        t_b = time.perf_counter()
        tr = DeviceResidentTrainer(
            list(leaves0), kv, grad_step, threshold=spec["threshold"],
            learning_rate=spec["lr"], momentum=spec["momentum"],
            **spec.get("trainer", {}))
        info["boot_s"] = time.perf_counter() - t_b
        batches = batches_of(w)
        if not mesh_party:
            batches = [jnp.asarray(b) for b in batches]
        with compile_lock:
            c0, t_c = compiles.seconds, time.perf_counter()
            tr.warmup(batches[0], None)
            info["warm_s"] = time.perf_counter() - t_c
            info["compile_s"] = compiles.seconds - c0
        info["k"] = tr.k
        win.warm.wait(WORKERS_S)    # every trainer warm before a round
        log = {}
        _recording(kv, log)
        mode, it = "step", 0
        while mode != "stop":
            timed_round = win.done >= 0
            batch = batches[it % len(batches)]
            t_s = time.perf_counter()
            with jax.profiler.TraceAnnotation(
                    f"bench.step w{w} r{win.done}"):
                if mode == "step_timed":
                    loss, phases = tr.step_timed(batch, None)
                    if timed_round:
                        info["timed"].append(phases)
                else:
                    loss = tr.step(batch, None)
            dt = time.perf_counter() - t_s
            if it == 0:
                _stop_recording(kv)
                info["log"] = log
                info["untimed_s"] = dt
                info["v_after"] = np.asarray(tr._v)
                if w == 0:
                    info["flat_after"] = np.asarray(tr._flat)
            if not timed_round:
                info["programs_at_start"] = compiles.programs
            info["losses"].append(loss)
            it += 1
            mode = win.meet()
        info["flat"] = np.asarray(tr._flat)
        if mesh_party:
            shards = [np.asarray(s.data)
                      for s in tr._flat.addressable_shards]
            info["replicas_equal"] = all(
                np.array_equal(shards[0].view(np.uint32),
                               s.view(np.uint32)) for s in shards[1:])

    topo.run_workers(worker, include_master=master_init, timeout=WORKERS_S)
    programs_end = compiles.programs
    mem_peak = _memory_peak()
    topo.stop()     # re-raises any node's error
    sizes = [l.size for l in leaves0]
    return finish(args, man, entry["chips"], cfg, spec, stamp, peaks, win,
                  res, flat0, sizes, grad0, topo.num_all, ref, checks,
                  limits, programs_end, mem_peak, compiles, trace_dir)


def finish(args, man, chips, cfg, spec, stamp, peaks, win, res, flat0,
           sizes, grad0, num_all, ref, checks, limits, programs_end,
           mem_peak, compiles, trace_dir) -> int:
    """After the topology has stopped: parts (b) and (c) of ``correct``,
    the metrics, the result line."""
    from geomx_tpu import telemetry

    n = len(res)
    t0, t_end = win.starts[0], win.ends[-1]
    rounds = len(win.ends)
    ctl = bool(args.controls)
    for w, r in enumerate(res):
        say(f"worker {w}: bootstrap {r['boot_s']:.1f} s, warm-up "
            f"{r['warm_s']:.1f} s (compile {r['compile_s']:.1f} s), selects "
            f"{r['k']} of {flat0.size} a round, untimed round "
            f"{r['untimed_s']:.2f} s, loss {r['losses'][0]:.4f} -> "
            f"{r['losses'][-1]:.4f}")

    # -- correct (b): the untimed round, select / aggregate / apply ------
    checks["select"] = True
    for w, r in enumerate(res):
        sel = correct.check_select(
            r["log"]["pushed"], r.pop("v_after"), sizes, spec["threshold"],
            grad=grad0 if w == 0 else None, workers=num_all)
        ok = not (sel["keys_bad_count"] or sel["keys_not_topk"]
                  or sel["not_cleared"])
        line = (f"correct (b) select, worker {w}: of {sel['keys']} keys, "
                f"not k distinct positions of their own "
                f"{sel['keys_bad_count']} (limit 0), not the exact top-k "
                f"by magnitude {sel['keys_not_topk']} (limit 0), "
                f"accumulator not cleared at {sel['not_cleared']} pushed "
                f"positions (limit 0)")
        if "grad_rel_l2" in sel:
            ok = ok and sel["grad_rel_l2"] <= limits["grad_rel_l2"]
            line += (f"; its accumulator before selection against the "
                     f"reference's gradient of its batch, grad_rel_l2 "
                     f"{sel['grad_rel_l2']:.6g} (limit "
                     f"{limits['grad_rel_l2']})")
        checks["select"] = checks["select"] and ok
        say(f"{line} -> {'ok' if ok else 'FAIL'}")
    agg = correct.check_aggregate([r["log"]["pushed"] for r in res],
                                  [r["log"]["applied"] for r in res])
    checks["aggregate"] = (agg["exact"] and agg["pusher_share_min"]
                           >= limits["pusher_share_min"])
    say(f"correct (b) aggregate: {agg['applied_entries']} applied of "
        f"{agg['pushed_positions']} pushed positions; twice "
        f"{agg['duplicates']} (limit 0), not pushed by anyone "
        f"{agg['not_pushed_by_anyone']} (limit 0), value not a float32 sum "
        f"of its pushers {agg['value_not_a_sum']} (limit 0), workers "
        f"agree {agg['workers_agree']}, smallest share of a pusher's "
        f"entries that came back {agg['pusher_share_min']:.4f} (at least "
        f"{limits['pusher_share_min']}); information: part of their "
        f"pushers only at {agg['part_of_its_pushers']}, every pushed "
        f"entry came back {agg['complete']} -> "
        f"{'ok' if checks['aggregate'] else 'FAIL'}")
    app = correct.check_apply(flat0, res[0].pop("flat_after"),
                              res[0]["log"]["applied"], sizes, spec["lr"],
                              control=ctl)
    checks["apply"] = (app["mismatched_untouched"] == 0
                       and app["max_ulps"] <= correct.APPLY_MAX_ULPS)
    say(f"correct (b) apply, worker 0: {app['touched']} parameters "
        f"touched, farthest from init - lr * aggregate in float32 "
        f"{app['max_ulps']:.3g} ulp of its largest term (limit "
        f"{correct.APPLY_MAX_ULPS}), "
        f"untouched parameters changed {app['mismatched_untouched']} "
        f"(limit 0)"
        + (f"; CONTROL bfloat16 apply {app['control_max_ulps']:.6g} ulp"
           if ctl else "")
        + f" -> {'ok' if checks['apply'] else 'FAIL'}")

    # -- correct (c): the outcome -----------------------------------------
    bits = [r["flat"].view(np.uint32) for r in res]
    identical = all(np.array_equal(bits[0], b) for b in bits[1:])
    moved = not np.array_equal(bits[0], flat0.view(np.uint32))
    finite = all(np.isfinite(r["losses"]).all() for r in res)
    late = programs_end - max(r["programs_at_start"] for r in res)
    snap0, snap1 = win.snaps[0], win.snaps[-1]
    wan_at = [telemetry.wan_bytes(s) for s in win.snaps]
    wan = wan_at[-1] - wan_at[0]
    by0 = telemetry.wan_bytes_by_codec(snap0)
    by_codec = {k: v - by0.get(k, 0.0) for k, v in
                telemetry.wan_bytes_by_codec(snap1).items()}
    raw = by_codec.get("raw", 0.0)
    replicas = all(r.get("replicas_equal", True) for r in res)
    checks.update(identical=identical, moved=moved, finite=finite,
                  no_late_compile=late == 0, wan_counted=wan > 0,
                  wan_not_raw=raw == 0,
                  replicas_equal=replicas)
    checks = {k: bool(v) for k, v in checks.items()}
    say(f"correct (c): workers bit-identical {identical}, moved from init "
        f"{moved}, losses finite {finite}, programs compiled after the "
        f"untimed round(s) {late} (limit 0), WAN bytes in window "
        f"{wan:.0f} (> 0), of them raw {raw:.0f} (limit 0; by codec "
        f"{({k: int(v) for k, v in by_codec.items()})}), mesh replicas "
        f"equal {replicas}")

    # -- metrics ------------------------------------------------------------
    seq, bpw = spec["seq_len"], spec["batch_per_worker"]
    window_s = t_end - t0
    tokens = rounds * n * bpw * seq
    setup_s = t0 - T_PROCESS
    r0 = (np.asarray(win.ends) - np.asarray(win.starts[:rounds])) * 1e3
    wan_rounds = min(WAN_ROUNDS, rounds)
    say(f"window {window_s:.3f} s, {rounds} rounds of {n * bpw * seq} "
        f"tokens; round ms, boundary to boundary: "
        f"{[round(float(x), 1) for x in r0]}, median "
        f"{np.median(r0):.1f}, p90 {np.percentile(r0, 90):.1f} (of "
        f"{len(r0)}: close to the slowest); WAN MB by round "
        f"{[round(float(x) / 1e6, 3) for x in np.diff(wan_at)]}, "
        f"wan_mb_per_round over the first {wan_rounds}; set-up "
        f"{setup_s:.1f} s; "
        f"{compiles.programs} programs built or loaded, "
        f"{compiles.cache_hits} from the persistent cache, "
        f"{compiles.seconds:.0f} s")
    metrics = {}
    device = dict(stamp, memory_peak_bytes=mem_peak)
    out = {"correct": bool(all(checks.values())),
           "attempted": len(res[0]["losses"]),
           "failed": 0, "metrics": metrics, "device": device,
           "checks": checks}
    if not args.trace:
        values = {
            "tokens_per_s_per_chip": tokens / window_s / chips,
            "round_ms_p90": float(np.percentile(r0, 90)),
            "wan_mb_per_round": (wan_at[wan_rounds] - wan_at[0])
            / wan_rounds / 1e6,
            "setup_s": setup_s,
        }
        for m in manifest.metrics_of(man, "end_to_end", args.workload):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        t_a, t_b, traced_rounds = win.traced
        trace = None
        try:
            trace = trace_reduce.reduce_dir(trace_dir, chips=chips,
                                            rounds=traced_rounds)
        except trace_reduce.NoDeviceOps:
            if not args.rehearse:
                raise
        ctx = readers.Context(
            cell=args.workload, chips=chips, peaks=peaks, rounds=rounds,
            timed=res[0]["timed"], snaps=win.snaps, trace=trace,
            tokens_traced=traced_rounds * n * bpw * seq, reference=ref,
            cfg=cfg, seq_len=seq)
        for m in manifest.metrics_of(man, "per_layer", args.workload):
            lm = manifest.layer_metric_spec(m["name"])
            value = manifest.resolve(lm["reader"])(ctx, lm)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        if trace is not None:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            out["breakdown"] = {"device_ops": trace["device_ops"],
                                "idle_gaps": trace["idle_gaps"]}
        say(f"traced {traced_rounds} rounds of step() in {t_b - t_a:.2f} s, "
            f"then {len(res[0]['timed'])} rounds of step_timed(); profiler "
            f"write-out {win.trace_write_s:.1f} s (in no round)")
    if args.rehearse:
        out["rehearsal"] = True
        say(f"DRY RUN complete: no chip result; exit {REHEARSAL_EXIT}")
    print(json.dumps(out), flush=True)
    if args.rehearse:
        return REHEARSAL_EXIT
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--controls", type=int, choices=(0, 1), default=0,
                    help="also print the controls of correct (b)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dry run at tiny widths; never a chip result")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    code = run(args)
    faulthandler.cancel_dump_traceback_later()
    return code


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 - report, then exit nonzero NOW
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # a topology leaves daemon and native threads behind; they must not
    # hold the exit open, and no result line follows a failure
    os._exit(code)
