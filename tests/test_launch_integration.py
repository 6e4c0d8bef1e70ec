"""Launch-path integration test: the REAL multi-process topology.

Spawns the full 12-process, 3-party HiPS demo through the same chain a
user runs — ``scripts/run_vanilla_hips.sh`` → ``hips_env.sh`` env-var
wiring → ``import geomx_tpu`` bootstrap for infra roles →
``examples/cnn.py`` workers — and asserts the observable correctness
signal the reference uses (climbing test accuracy on the foreground
worker, reference: scripts/cpu/run_vanilla_hips.sh:8-148 + cnn.py:129).

This covers exactly the path in-process tests cannot: env-var config
parsing, the import-time server bootstrap (kvstore_server.py), process
isolation, and clean exit cascades. The round-1 startup-deadlock
regression shipped through this path while every in-process test stayed
green.
"""

import sys

import pytest

from tests.harness import _run_launch


@pytest.mark.slow
def test_vanilla_hips_subprocess_topology():
    accs = _run_launch("run_vanilla_hips.sh", [], n_iters=15, timeout=240)
    # the correctness signal: training must actually learn (random = 0.1)
    assert max(accs[-5:]) > 0.4, f"accuracy did not climb: {accs}"
    assert max(accs[-5:]) > accs[0], f"accuracy did not improve: {accs}"


@pytest.mark.slow
def test_bsc_subprocess_topology():
    """The BASELINE headline config through the REAL launch chain:
    cnn_bsc.py (aggregator PS, worker-side Adam, BSC both directions).

    Assertion calibration: sparse-top-k trajectories are chaotically
    run-to-run variable (near-tie index selections flip on float
    summation order), so a fixed-iteration accuracy bar flakes.
    What this test exists to catch is (a) the launch machinery — boot,
    N iterations, clean exit cascade — and (b) the frozen-training
    regression mode where pulls return nothing and accuracy pins at
    chance (~0.097) for the whole run. Measured over 5 calibration
    runs, every healthy run peaked >= 0.20 by iter 40 while the frozen
    mode never left 0.097."""
    accs = _run_launch("run_bsc.sh", ["-cr", "0.2"], n_iters=40,
                       timeout=360)
    # late-window bars so a mid-run freeze is caught too
    assert max(accs[-10:]) > 0.15, \
        f"BSC training frozen at chance: {accs}"
    assert len(set(accs[-20:])) > 3, f"accuracy never moved: {accs}"



@pytest.mark.slow
def test_mixed_sync_subprocess_topology():
    """MixedSync (dist_async: per-push global updates, no global
    barrier) through the real launch chain. Deterministic across runs
    (two calibration trials produced identical curves)."""
    accs = _run_launch("run_mixed_sync.sh", [], n_iters=15, timeout=240)
    assert max(accs[-5:]) > 0.3, f"MixedSync did not learn: {accs}"
    assert max(accs[-5:]) > accs[0], f"no improvement: {accs}"


@pytest.mark.slow
def test_hfa_subprocess_topology():
    """HFA (K1 local steps per LAN sync, K2-periodic WAN rounds)
    through the real launch chain; prints every K1=2 iterations.
    Deterministic (two calibration trials identical: 0.7471 @ 20)."""
    accs = _run_launch("run_hfa.sh", [], n_iters=20, timeout=240,
                       expect_lines=10)
    assert max(accs[-4:]) > 0.5, f"HFA did not learn: {accs}"


@pytest.mark.slow
def test_fp16_subprocess_topology():
    """FP16 wire transmission through the real launch chain
    (deterministic: calibration trials identical, 0.6934 @ 15)."""
    accs = _run_launch("run_fp16.sh", [], n_iters=15, timeout=240)
    assert max(accs[-5:]) > 0.5, f"FP16 did not learn: {accs}"


@pytest.mark.slow
def test_mpq_subprocess_topology():
    """MPQ (size-threshold fp16/bsc routing) through the real launch
    chain (near-deterministic: 0.775-0.782 @ 25 across trials; the BSC
    component adds slight variance)."""
    accs = _run_launch("run_mpq.sh", [], n_iters=25, timeout=300)
    assert max(accs[-8:]) > 0.5, f"MPQ did not learn: {accs}"


# ---------------------------------------------------------------------------
# round-4: the remaining 6 feature scripts (round-3 verdict item 5 —
# DGT, P3, TS pair, MultiGPS, DCASGD had only in-process coverage; the
# round-1 regression shipped through exactly this untested env-var ->
# bootstrap -> subprocess glue). Marked slow: the default CI tier runs
# `pytest -m "not slow"`; these belong to the nightly/full tier.
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_p3_subprocess_topology():
    """P3 priority scheduling (ENABLE_P3=1: bigarray-granularity key
    slicing + priority send queues) through the real launch chain."""
    accs = _run_launch("run_p3.sh", [], n_iters=15, timeout=300)
    assert max(accs[-5:]) > 0.4, f"P3 did not learn: {accs}"
    assert max(accs[-5:]) > accs[0], f"no improvement: {accs}"


@pytest.mark.slow
def test_multi_gps_subprocess_topology():
    """MultiGPS (DMLC_NUM_GLOBAL_SERVER=2): 13 processes, keys shard
    across two global servers by the canonical heuristic."""
    accs = _run_launch("run_multi_gps.sh", [], n_iters=15, timeout=300)
    assert max(accs[-5:]) > 0.4, f"MultiGPS did not learn: {accs}"
    assert max(accs[-5:]) > accs[0], f"no improvement: {accs}"


@pytest.mark.slow
def test_dcasgd_subprocess_topology():
    """DCASGD (dist_async + delay compensation at the global tier)
    through the real launch chain. Async trajectories are noisy —
    the bar is leaving chance decisively, not a fixed curve."""
    accs = _run_launch("run_dcasgd.sh", ["-lr", "0.05"], n_iters=60,
                       timeout=420)
    assert max(accs) > 0.3, f"DCASGD did not learn: {accs}"
    assert len(set(accs[-20:])) > 3, f"accuracy never moved: {accs}"


@pytest.mark.slow
def test_dgt_udp_subprocess_topology():
    """DGT mode 1: unimportant gradient blocks ride lossy UDP channels
    on the inter-DC tier (ENABLE_DGT=1, DMLC_UDP_CHANNEL_NUM=3)."""
    accs = _run_launch("run_dgt.sh", [], n_iters=20, timeout=300,
                       env_extra={"ENABLE_DGT": "1"})
    assert max(accs[-5:]) > 0.3, f"DGT/UDP did not learn: {accs}"


@pytest.mark.slow
def test_dgt_quantized_subprocess_topology():
    """DGT mode 3: unimportant blocks 4-bit quantized over TCP."""
    accs = _run_launch("run_dgt.sh", [], n_iters=20, timeout=300,
                       env_extra={"ENABLE_DGT": "3"})
    assert max(accs[-5:]) > 0.3, f"DGT/quantized did not learn: {accs}"


@pytest.mark.slow
def test_intra_ts_subprocess_topology():
    """Intra-DC TSEngine: worker-to-worker merge overlays built by the
    party scheduler (ENABLE_INTRA_TS=1)."""
    accs = _run_launch("run_intra_ts.sh", [], n_iters=15, timeout=300)
    assert max(accs[-5:]) > 0.3, f"intra-TS did not learn: {accs}"


@pytest.mark.slow
def test_inter_ts_subprocess_topology():
    """Inter-DC TSEngine: party-to-party aggregate merge on the WAN
    tier (ENABLE_INTER_TS=1)."""
    accs = _run_launch("run_inter_ts.sh", [], n_iters=15, timeout=300)
    assert max(accs[-5:]) > 0.3, f"inter-TS did not learn: {accs}"


@pytest.mark.slow
def test_transformer_bsc_subprocess_topology():
    """The round-4 flagship: a transformer through the device-resident
    BSC trainer (element-sparse wire) in the real 12-process topology.
    Small dims keep the 12 jax compiles tractable; the loss lines are
    the learning signal (transformer_bsc_device.py prints Loss, not
    Test Acc)."""
    losses = _run_launch(
        "run_transformer_bsc.sh",
        ["--cpu", "--dim", "64", "--depth", "2", "--heads", "4",
         "--vocab", "256", "--seq-len", "64", "-bs", "4"],
        n_iters=12, timeout=360, pattern=r"Loss (\d+\.\d+)")
    assert min(losses[-6:]) < losses[0], f"no learning: {losses}"


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-x", "-q"]))


@pytest.mark.slow
def test_esync_subprocess_topology():
    """ESync (beyond parity: reference README.md:45 documents it, ships
    no code) through the real launch chain: per-party state server
    assigns local step counts, synchronous model averaging. Uniform
    hosts here, so the signal is boot + learn + clean exit; the
    heterogeneity balancing itself is asserted in tests/test_esync.py."""
    accs = _run_launch("run_esync.sh", ["-r", "25", "-lr", "0.01"],
                       n_iters=0, timeout=240, expect_lines=1,
                       pattern=r"final acc=(\d+\.\d+)",
                       pass_max_iters=False)
    # calibration: the same config in-process reaches 0.73 @ 25 rounds
    assert accs[0] > 0.5, f"ESync did not learn: {accs}"
