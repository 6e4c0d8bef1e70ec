#!/usr/bin/env python
"""Microbench: a party server's Bi-Sparse re-selection of one round here.

A round's WAN-forward re-selection is ``compression.bsc_compress`` a key
(``kvstore/server.py::_flush_forward_batch``): host memory bandwidth,
not the chip. This reads what the host gives it: the cores, then a
cell's key sizes through the real function, as the numpy passes and as
the native sweep (``native/kernels.cc``), over 1..N threads a server,
one server alone and two at once (a cell's two party servers share the
host; the second selects alone while the chip idles). It also holds the
sweep to the numpy passes bit for bit on this processor. Host timings
only: nothing here touches a device, and no number it prints is a
device metric. ``_POOL_HELPERS`` in ``kvstore/server.py`` was set from
its readings on the chip machine (``PERF.md`` section 6, PR 37).

Usage: python tools/select_bench.py [--cells laguna,gpt2s] [--threads 1,2,3,4]
                                    [--rounds 3] [--topo] [--positions]
``--topo`` keeps a live two-party topology idle beside the measurement.
``--positions`` reads the other per-key pass of the party-global hop
and nothing else: ns a position to code and to decode a cell's keys'
positions (``compression.entries.CODED``) at 1% of each key (a party's
forward) and at 2% (two parties' union, the pull-back), the native form
and the numpy form, one thread.
"""

import argparse
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from geomx_tpu import kernels_native  # noqa: E402
from geomx_tpu.compression import (Pairs, bsc_compress,  # noqa: E402
                                   bsc_sample_positions)
from geomx_tpu.compression import entries as coding  # noqa: E402
from geomx_tpu.kvstore.server import _SelectPool  # noqa: E402

THRESHOLD = 0.01
# the key sizes of the benchmark's cells, rounded (PERF.md section 4)
CELLS = {
    "gpt2s": [38_597_376] * 2 + [2_359_296] * 24 + [1_769_472] * 12
    + [589_824] * 12 + [786_432] + [3_072] * 50 + [768] * 49,
    "olmoe": [33_554_432] * 3 + [25_755_648] * 2 + [4_194_304] * 4
    + [131_072] + [2_048] * 5,
    "laguna": [25_690_112] * 2 + [16_777_216] * 3 + [8_388_608] * 12
    + [2_097_152] * 8 + [1_048_576] * 12 + [262_144] * 20 + [2_048] * 12,
    "qwen3next": [38_895_616] * 2 + [16_777_216] * 2 + [8_388_608] * 12
    + [4_194_304] * 10 + [1_048_576] * 12 + [65_536] * 20 + [16] * 12,
}


def numpy_passes(on: bool, _real=kernels_native.bsc_pass_usable) -> None:
    """Make ``bsc_compress`` run its numpy passes (or the sweep again)."""
    kernels_native.bsc_pass_usable = (lambda u, v: False) if on else _real


class Server:
    """One party server's Bi-Sparse state for ``sizes``, and its round."""

    def __init__(self, sizes, seed):
        self.rng = np.random.default_rng(seed)
        self.draws = np.random.default_rng(42)
        self.keys = [(np.zeros(n, np.float32), np.zeros(n, np.float32))
                     for n in sizes]

    def pairs(self):
        out = []
        for u, _v in self.keys:
            n = u.size
            idx = np.sort(self.rng.choice(n, max(n // 100, 1), replace=False,
                                          shuffle=False)).astype(np.int32)
            out.append(Pairs(idx, self.rng.standard_normal(
                idx.size, dtype=np.float32), n))
        return out

    def round(self, pairs, threads):
        """As ``_stage_forwards``: the draws first, in key order; the
        large keys largest first over the server's own kind of pool,
        ``threads`` with this one, the small ones on this thread."""
        drawn = [bsc_sample_positions(u.size, THRESHOLD, self.draws)
                 for u, _v in self.keys]

        def one(i):
            bsc_compress(pairs[i], *self.keys[i], THRESHOLD,
                         positions=drawn[i])

        if threads <= 1:
            for i in range(len(self.keys)):
                one(i)
            return
        pool = _SelectPool(threads - 1)
        try:
            pool.run_sized([u.size for u, _v in self.keys], one)
        finally:
            pool.close()


def identical(n, seed) -> bool:
    """Three rounds of a key of ``n``: the sweep's values, positions,
    ``u`` and ``v`` against the numpy passes'."""
    r = np.random.default_rng(seed)
    u = (0.01 * r.standard_normal(n)).astype(np.float32)
    v = r.standard_normal(n).astype(np.float32)
    ru, rv = u.copy(), v.copy()
    for _ in range(3):
        k = max(n // 100, 1)
        idx = r.choice(n, k, replace=False).astype(np.int32)
        idx[:k // 5] = idx[k // 5:2 * (k // 5)]
        grad = Pairs(idx, r.standard_normal(k).astype(np.float32), n)
        pos = bsc_sample_positions(n, THRESHOLD, r)
        numpy_passes(False)
        a = bsc_compress(grad, u, v, THRESHOLD, positions=pos)
        numpy_passes(True)
        b = bsc_compress(grad, ru, rv, THRESHOLD, positions=pos)
        numpy_passes(False)
        if not (a[0].tobytes() == b[0].tobytes()
                and a[1].tobytes() == b[1].tobytes()
                and u.tobytes() == ru.tobytes()
                and v.tobytes() == rv.tobytes()):
            return False
    return True


def timed_rounds(servers, threads, rounds):
    out = []
    for _ in range(rounds):
        pairs = [s.pairs() for s in servers]
        start = threading.Barrier(len(servers) + 1)

        def run(s, p):
            start.wait()
            s.round(p, threads)

        running = [threading.Thread(target=run, args=sp)
                   for sp in zip(servers, pairs)]
        for t in running:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        for t in running:
            t.join()
        out.append(1e3 * (time.perf_counter() - t0))
    return out


def positions_bench(cell: str, rounds: int) -> None:
    """A line a density: the cell's keys' positions through the code
    and back, the best of ``rounds`` passes over all keys, as ns a
    position."""
    forms = {"numpy": (coding.encode_positions_numpy,
                       coding.decode_positions_numpy)}
    if kernels_native.lib() is not None:
        forms["native"] = (coding.encode_positions, coding.decode_positions)
    rng = np.random.default_rng(3)
    for density in (0.01, 0.02):
        lists = [(np.sort(rng.choice(n, max(int(n * density), 1),
                                     replace=False, shuffle=False)
                          ).astype(np.int32), n) for n in CELLS[cell]]
        count = sum(idx.size for idx, _n in lists)
        out = {}
        for name, (encode, decode) in forms.items():
            enc, dec = [], []
            for _ in range(rounds):
                t0 = time.perf_counter()
                coded = [encode(idx) for idx, _n in lists]
                t1 = time.perf_counter()
                back = [decode(c, idx.size, n)
                        for c, (idx, n) in zip(coded, lists)]
                t2 = time.perf_counter()
                enc.append(t1 - t0)
                dec.append(t2 - t1)
            assert all((b == idx).all() for b, (idx, _n) in zip(back, lists))
            out[name] = (f"encode {1e9 * min(enc) / count:6.2f} decode "
                         f"{1e9 * min(dec) / count:6.2f} ns a position")
        nbytes = sum(c.size for c in coded)
        print(f"{cell} positions at {density:.0%}: {count} in "
              f"{len(lists)} keys, {nbytes / count:.3f} bytes a position "
              f"coded; " + "; ".join(f"{k}: {v}" for k, v in out.items()),
              flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default="laguna,gpt2s")
    ap.add_argument("--threads", default="1,2,3,4,6")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--topo", action="store_true")
    ap.add_argument("--positions", action="store_true")
    args = ap.parse_args()
    threads = [int(t) for t in args.threads.split(",")]

    print("cpu_count", os.cpu_count(), "affinity",
          len(os.sched_getaffinity(0)), flush=True)
    if args.positions:
        for cell in args.cells.split(","):
            positions_bench(cell, args.rounds)
        return
    topo = None
    if args.topo:
        from geomx_tpu.simulate import InProcessHiPS

        topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
        print("a live topology idle beside:", threading.active_count(),
              "threads", flush=True)
    try:
        if kernels_native.lib() is None:
            print("no native kernels here: the numpy passes alone")
            modes = ["numpy"]
        else:
            print("sweep == numpy passes, bit for bit:",
                  [identical(n, 5 + i) for i, n in enumerate(
                      (16_384, 100_003, 2_359_296, 8_388_608))], flush=True)
            modes = ["numpy", "sweep"]
        for cell in args.cells.split(","):
            sizes = CELLS[cell]
            servers = [Server(sizes, 11), Server(sizes, 12)]
            print(f"{cell}: {len(sizes)} keys, {sum(sizes) / 1e6:.1f}M "
                  f"elements, largest {max(sizes) / 1e6:.1f}M", flush=True)
            timed_rounds(servers, 1, 1)         # first touch of the state
            for mode in modes:
                numpy_passes(mode == "numpy")
                for at_once in (1, 2):
                    for t in threads:
                        ms = timed_rounds(servers[:at_once], t, args.rounds)
                        print(f"{cell} {mode}: {at_once} server(s) at once, "
                              f"{t} thread(s) a server: rounds "
                              + " ".join(f"{x:7.1f}" for x in ms) + " ms",
                              flush=True)
            numpy_passes(False)
            del servers
    finally:
        if topo is not None:
            topo.stop()


if __name__ == "__main__":
    main()
