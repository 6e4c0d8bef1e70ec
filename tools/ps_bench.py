#!/usr/bin/env python
"""PS protocol benchmark: the HiPS stack's intrinsic round latency and
throughput, NO accelerator in the loop.

Measures full two-tier rounds (2 parties x 1 worker -> party servers ->
global server -> pull-back) for numpy payloads of several sizes. This
isolates the framework's own speed from device effects — the
complement of bench.py's framework-in-the-loop numbers.

Prints one JSON line per payload size:
  {"elems": N, "rounds_per_s": R, "round_ms": L, "mb_per_s": B}
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from geomx_tpu.optimizer import SGD                 # noqa: E402
from geomx_tpu.simulate import InProcessHiPS       # noqa: E402

SIZES = [1_024, 65_536, 1_048_576]
SECONDS = 5.0


def bench_size(n_elems: int) -> dict:
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    try:
        topo.master.set_optimizer(SGD(learning_rate=0.01))
        time.sleep(0.3)
        w0 = np.zeros(n_elems, np.float32)
        rounds = [0, 0]
        stop_round = [None]
        errs: list = []

        def master(kv):
            kv.init(0, w0)
            kv.wait()

        def worker(kv):
            widx = 0 if kv is topo.workers[0] else 1
            kv.init(0, w0)
            kv.pull(0, out=np.zeros_like(w0))
            kv.wait()
            grad = np.ones(n_elems, np.float32)
            out = np.zeros(n_elems, np.float32)
            while stop_round[0] is None or rounds[widx] < stop_round[0]:
                kv.push(0, grad)
                kv.pull(0, out=out)
                kv.wait()
                rounds[widx] += 1

        def run():
            try:
                topo.run_workers(worker, include_master=master,
                                 timeout=600.0)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        deadline = time.monotonic() + 60
        while sum(rounds) < 4 and time.monotonic() < deadline:
            if errs:
                raise errs[0]
            time.sleep(0.05)
        r0 = sum(rounds)
        t0 = time.perf_counter()
        time.sleep(SECONDS)
        made = sum(rounds) - r0
        dt = time.perf_counter() - t0
        if errs:
            raise errs[0]
        stop_round[0] = max(rounds) + 2
        t.join(60)
        # per-worker round rate (both workers advance in lockstep)
        rps = made / 2 / dt
        # bytes per ROUND per worker: push grad + pull params on the LAN
        # hop, plus the party->global->party WAN exchange (counted once
        # per party = per worker here)
        bytes_per_round = 4 * n_elems * 4
        return {
            "elems": n_elems,
            "rounds_per_s": round(rps, 1),
            "round_ms": round(1000.0 / rps, 3) if rps else None,
            "mb_per_s": round(rps * bytes_per_round / 1e6, 1),
        }
    finally:
        topo.stop()


def main():
    for n in SIZES:
        print(json.dumps(bench_size(n)), flush=True)


if __name__ == "__main__":
    main()
