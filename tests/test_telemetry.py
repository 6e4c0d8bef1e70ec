"""Metrics registry tests: semantics, cost-when-disabled, and the
kv.metrics() / wan_bytes() paths over a live 2-party topology.

The acceptance bar this file carries: disabled-telemetry overhead stays
under 5% of a 10-key loopback round, and wan_bytes() equals the manual
sum of the per-verb global-tier send counters (the figure
benchmark/run.py reports as wan_mb_per_round).
"""

import json
import time

import numpy as np
import pytest

from geomx_tpu import profiler, telemetry
from geomx_tpu.optimizer import SGD
from geomx_tpu.simulate import InProcessHiPS

from tests.harness import SingleTier, _parallel


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.reset()
    profiler.reset()
    yield
    telemetry.reset()
    profiler.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_disabled_mutators_record_nothing():
    assert not telemetry.enabled()
    telemetry.counter_inc("c", 5, tier="local")
    telemetry.gauge_set("g", 7)
    telemetry.histogram_obs("h", 3)
    snap = telemetry.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {}


def test_counter_labels_render_sorted():
    telemetry.enable(True)
    telemetry.counter_inc("van.bytes_sent", 10, verb="push", tier="local")
    telemetry.counter_inc("van.bytes_sent", 4, tier="local", verb="push")
    telemetry.counter_inc("plain")
    snap = telemetry.snapshot()
    # label order in the call does not matter: one key, sorted labels
    assert snap["counters"]["van.bytes_sent{tier=local,verb=push}"] == 14
    assert snap["counters"]["plain"] == 1


def test_counters_add_books_rows_under_the_labels_counter_inc_builds():
    """The round account's way in: many rows, one hold of the lock, the
    keys ``counter_inc`` would have made."""
    telemetry.enable(True)
    telemetry.counter_inc("round.spans", 2, span="van.send")
    telemetry.counters_add([
        ("round.spans", (("span", "van.send"),), 3),
        ("round.work_ms", (("span", "van.send"),), 1.5),
        ("host.gc_ms", (("gen", 2),), 0.25)])
    assert telemetry.snapshot()["counters"] == {
        "round.spans{span=van.send}": 5,
        "round.work_ms{span=van.send}": 1.5, "host.gc_ms{gen=2}": 0.25}


def test_gauge_last_value_wins():
    telemetry.enable(True)
    telemetry.gauge_set("epoch", 1)
    telemetry.gauge_set("epoch", 3)
    assert telemetry.snapshot()["gauges"]["epoch"] == 3


def test_histogram_buckets_and_overflow():
    telemetry.enable(True)
    telemetry.histogram_obs("lat", 3)        # -> bucket ub=5
    telemetry.histogram_obs("lat", 3)
    telemetry.histogram_obs("lat", 99999)    # -> overflow bucket
    h = telemetry.snapshot()["histograms"]["lat"]
    assert h["count"] == 3 and h["sum"] == 3 + 3 + 99999
    assert h["min"] == 3 and h["max"] == 99999
    idx5 = telemetry.BUCKETS.index(5)
    assert h["buckets"][idx5] == 2
    assert h["buckets"][-1] == 1
    assert sum(h["buckets"]) == h["count"]


def test_configure_none_leaves_settings_untouched():
    telemetry.enable(True)
    telemetry.configure(enabled=None, export_dir=None)
    assert telemetry.enabled()
    # the InProcessHiPS property: a later node's Config(telemetry=False)
    # must not switch off a registry another node enabled
    telemetry.configure(enabled=None)
    assert telemetry.enabled()
    telemetry.configure(enabled=False)
    assert not telemetry.enabled()


def test_event_counts_when_enabled_and_feeds_profiler():
    profiler.set_state("run")
    telemetry.event("sanitizer.violation", kind="unanswered")
    # profiler sees the instant even with telemetry off...
    names = [e["name"] for e in json.loads(profiler.dumps())["traceEvents"]]
    assert "sanitizer.violation" in names
    assert telemetry.snapshot()["counters"] == {}
    # ...and the registry counts it once enabled
    telemetry.enable(True)
    telemetry.event("sanitizer.violation", kind="unanswered")
    telemetry.event("sanitizer.violation", kind="unanswered")
    assert telemetry.snapshot()["counters"][
        "event.sanitizer.violation"] == 2


def test_sample_sets_gauge_and_counter_track():
    profiler.set_state("run")
    telemetry.enable(True)
    telemetry.sample("queue.depth", 4)
    assert telemetry.snapshot()["gauges"]["queue.depth"] == 4
    evs = json.loads(profiler.dumps())["traceEvents"]
    assert any(e["name"] == "queue.depth" and e["ph"] == "C" for e in evs)


def test_reset_clears_and_disables():
    telemetry.enable(True)
    telemetry.counter_inc("c")
    telemetry.reset()
    assert not telemetry.enabled()
    assert telemetry.snapshot()["counters"] == {}


def test_export_round_atomic(tmp_path):
    telemetry.enable(True)
    telemetry.counter_inc("c", 2)
    assert telemetry.export_round(1) == ""   # no dir configured
    path = telemetry.export_round(7, str(tmp_path))
    assert path.endswith("_pid") is False and "metrics_round7_pid" in path
    doc = json.loads(open(path).read())
    assert doc["counters"]["c"] == 2
    # atomic: no tmp files left behind
    assert all(".tmp." not in p.name for p in tmp_path.iterdir())


def test_snapshot_schema_pinned():
    """Gate: the snapshot document shape downstream consumers (health
    board, transport controller) parse. Changing the top-level keys, the
    histogram value shape, or the version REQUIRES bumping
    ``telemetry.SCHEMA_VERSION`` and updating this test in the same
    change."""
    telemetry.enable(True)
    telemetry.counter_inc("c", 1)
    telemetry.gauge_set("g", 2.0)
    telemetry.histogram_obs("h", 3.0)
    snap = telemetry.snapshot()
    assert snap["schema_version"] == telemetry.SCHEMA_VERSION == 2
    assert set(snap) == {"schema_version", "counters", "gauges",
                         "histograms", "bucket_bounds", "slow_rounds"}
    assert snap["slow_rounds"] == []
    assert set(snap["histograms"]["h"]) == {"count", "sum", "min", "max",
                                            "buckets"}
    assert snap["bucket_bounds"] == list(telemetry.BUCKETS)
    # the JSON form carries the same version (what export_round writes)
    assert json.loads(telemetry.snapshot_json())["schema_version"] == 2


def test_wan_bytes_sums_global_send_counters_only():
    telemetry.enable(True)
    telemetry.counter_inc("van.bytes_sent", 100, tier="global", verb="push",
                          codec="raw")
    telemetry.counter_inc("van.bytes_sent", 40, tier="global", verb="pull",
                          codec="raw")
    telemetry.counter_inc("van.bytes_sent", 7, tier="global", verb="command",
                          codec="raw")
    telemetry.counter_inc("van.bytes_sent", 999, tier="local", verb="push",
                          codec="raw")           # LAN: not WAN traffic
    telemetry.counter_inc("van.bytes_recv", 888, tier="global", verb="push",
                          codec="raw")           # recv side: not counted
    snap = telemetry.snapshot()
    manual = sum(v for k, v in snap["counters"].items()
                 if k.startswith("van.bytes_sent{") and "tier=global" in k)
    assert manual == 147
    assert telemetry.wan_bytes() == manual
    assert telemetry.wan_bytes(snap) == manual


def test_wan_bytes_excludes_mesh_tier_counters():
    """The mesh-party tier's device collectives (kvstore.mesh_party)
    live under their own counter family: wan_bytes() must never count
    them — they cross ICI inside one DC, not the WAN — and
    mesh_bytes() must count exactly them."""
    telemetry.enable(True)
    telemetry.counter_inc("van.bytes_sent", 100, tier="global", verb="push",
                          codec="raw")
    telemetry.counter_inc("mesh.bytes", 4096, tier="mesh", op="psum")
    telemetry.counter_inc("mesh.bytes", 512, tier="mesh", op="all_gather")
    telemetry.counter_inc("mesh.messages", 2, tier="mesh", op="psum")
    snap = telemetry.snapshot()
    assert telemetry.wan_bytes(snap) == 100
    assert telemetry.mesh_bytes(snap) == 4608
    # and the families are disjoint by construction
    assert telemetry.wan_bytes(snap) + telemetry.mesh_bytes(snap) == 4708


def test_mesh_store_count_collective_counter_family():
    """KVStorePartyMesh.count_collective books ring-model bytes
    (2*(P-1)*nbytes) under tier=mesh only, plus a message count."""
    from geomx_tpu.kvstore.mesh_party import KVStorePartyMesh

    telemetry.enable(True)
    store = object.__new__(KVStorePartyMesh)
    store.party_size = 4
    store.mesh_codec = "none"
    KVStorePartyMesh.count_collective(store, 1000)
    snap = telemetry.snapshot()
    assert telemetry.mesh_bytes(snap) == 6000     # 2*(4-1)*1000
    assert telemetry.wan_bytes(snap) == 0
    msgs = [v for k, v in snap["counters"].items()
            if k.startswith("mesh.messages{")]
    assert msgs == [1]
    # quantized codec: bytes follow the ring wire model under its own
    # codec= label, still structurally outside the WAN bill
    store.mesh_codec = "int8"
    store.mesh_block = 256
    KVStorePartyMesh.count_collective(store, 1000, op="ring")
    snap = telemetry.snapshot()
    from geomx_tpu.parallel.quant_collectives import ring_wire_bytes

    assert telemetry.mesh_bytes(snap) == 6000 + ring_wire_bytes(
        "int8", 250, 4, 256)
    assert telemetry.wan_bytes(snap) == 0
    assert any("codec=int8" in k and "op=ring" in k
               for k in snap["counters"] if k.startswith("mesh.bytes{"))


# ---------------------------------------------------------------------------
# disabled-overhead microbench + live topology
# ---------------------------------------------------------------------------

def _ten_key_round_seconds():
    """Measure one 10-key push+pull round on a single-tier loopback PS
    (same harness as test_profiler's end-to-end test)."""
    with SingleTier(num_workers=1) as topo:
        (kv,) = topo.workers
        kv.set_optimizer(SGD(learning_rate=1.0))
        for k in range(10):
            kv.init(k, np.ones(8, np.float32))
        kv.wait()
        t0 = time.perf_counter()
        for k in range(10):
            kv.push(k, np.ones(8, np.float32))
        for k in range(10):
            kv.pull(k)
        kv.wait()
        return time.perf_counter() - t0


def test_disabled_overhead_under_5pct_of_ten_key_round():
    """Acceptance bar: with telemetry off, the registry's cost on a
    10-key round is <5% of the round. A 10-key round is ~40 wire
    messages; each message touches the registry a handful of times
    (enabled() gate + the _note_wire mutators), so 400 disabled calls
    per round is a generous over-estimate."""
    assert not telemetry.enabled()
    N = 20000
    t0 = time.perf_counter()
    for i in range(N):
        telemetry.enabled()
        telemetry.counter_inc("van.bytes_sent", i, tier="local", verb="push")
        telemetry.gauge_set("g", i)
        telemetry.histogram_obs("h", i)
    per_call = (time.perf_counter() - t0) / (4 * N)
    round_s = _ten_key_round_seconds()
    est_overhead = per_call * 400
    assert est_overhead < 0.05 * round_s, (
        f"disabled telemetry would cost {est_overhead * 1e6:.1f}us on a "
        f"{round_s * 1e3:.1f}ms round")


def test_kv_metrics_and_wan_bytes_over_hips():
    """2-party HiPS round with telemetry on: kv.metrics() answers with
    the worker's and the servers' snapshots, the global tier counted
    WAN bytes, and wan_bytes() matches the manual per-verb sum — the
    cross-check the benchmark's wan_mb_per_round rests on."""
    telemetry.enable(True)
    sim = InProcessHiPS(num_parties=2, workers_per_party=1).start(
        sync_global=True)
    try:
        sim.master.set_optimizer(SGD(learning_rate=1.0))
        w0 = np.zeros(64, np.float32)

        def init_on(kv):
            kv.init(0, w0)
            kv.wait()

        _parallel([lambda kv=kv: init_on(kv)
                   for kv in sim.workers + [sim.master]])

        def step(kv):
            kv.push_pull(0, np.ones(64, np.float32),
                         np.zeros(64, np.float32))
            kv.wait()

        _parallel([lambda kv=kv: step(kv) for kv in sim.workers])

        got = sim.workers[0].metrics()
        assert "worker" in got and got["servers"]
        wsnap = got["worker"]
        # in-process sim: every node feeds one registry, so the worker
        # snapshot already carries van counters from both tiers
        sent = {k: v for k, v in wsnap["counters"].items()
                if k.startswith("van.bytes_sent{")}
        assert sent, "no send byte counters recorded"
        assert any("tier=global" in k for k in sent), \
            "no WAN-tier traffic counted"
        assert any("tier=local" in k for k in sent)
        # per-verb cross-check: wan_bytes() == sum of global send counters
        manual = sum(v for k, v in sent.items() if "tier=global" in k)
        assert manual > 0
        assert telemetry.wan_bytes(wsnap) == manual
        assert telemetry.wan_bytes() == pytest.approx(
            sum(v for k, v in telemetry.snapshot()["counters"].items()
                if k.startswith("van.bytes_sent{") and "tier=global" in k))
        # message counters ride along with matching labels
        assert any(k.startswith("van.messages_sent{")
                   for k in wsnap["counters"])
        # the server's answer is a valid snapshot of the same registry,
        # its share of the slow rounds' records included
        assert all("counters" in s for s in got["servers"])
        assert all(s["schema_version"] == 2 and s["slow_rounds"] == []
                   for s in got["servers"] + [wsnap])
        # no trainer in this process: the servers' spans were merged
        # when the round's id first showed, and at the snapshot
        assert wsnap["counters"]["round.spans{span=server.push}"] >= 2
        assert wsnap["counters"]["round.work_ms{span=van.send}"] > 0
    finally:
        sim.stop()


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
