"""The ``olmoe`` family as benchmark data: its count of required
operations against a hand count, the grouped-matmul roofline reader on
a synthetic run, and the cell's CPU rehearsal from a copy of the
checkout's benchmark files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest, moe_readers, readers

CELL = "olmoe-ep4-hips-bsc"


def _cfg():
    return manifest.load_config_file("olmoe-1b-7b-ep4")


def test_olmoe_share_hand_count():
    cfg = _cfg()
    ref = manifest.family_module("references", cfg["family"])
    # one layer at T=4096: q, k, v, o 8 * 2048^2 = 33,554,432; causal
    # attention 4 * 2048 * 2048.5 = 16,781,312; router 2 * 2048 * 64 =
    # 262,144; experts 8 * 16/64 = 2 rows a token, 6 * 2048 * 1024 each
    # = 25,165,824: 75,763,712. Head 2 * 2048 * 12576 = 51,511,296.
    assert cfg["num_hidden_layers"] == 1
    assert ref.forward_flops_per_token(cfg, 4096) == 127_275_008
    assert ref.train_flops_per_token(cfg, 4096) == 381_825_024
    assert ref.num_params(cfg) == cfg["sizes"]["parameters"] == 169_093_120
    assert len(ref.param_shapes(cfg)) == cfg["sizes"]["keys"] == 15


def test_configuration_keeps_every_published_width():
    cfg = _cfg()
    cut = set(cfg["reduced"])
    assert cut == {"num_hidden_layers", "vocab_size", "num_local_experts"}
    for key, value in cfg["published"].items():
        assert (cfg[key] == value) != (key in cut), key
    assert cfg["local_experts"] == [0, cfg["num_local_experts"]]


def _ctx(snaps, op_seconds, rounds=2):
    return readers.Context(
        cell=CELL, chips=1, peaks=manifest.peaks_for("TPU v5 lite"),
        rounds=4, timed=[], snaps=snaps,
        trace={"rounds": rounds, "op_seconds_first_chip": op_seconds},
        tokens_traced=rounds * 2 * 8 * 4096, reference=None, cfg=_cfg(),
        seq_len=4096)


def _snaps(per_round_local, per_round_total, n=5):
    return [{"counters": {"moe.rows_local": 7.0 + i * per_round_local,
                          "moe.rows_total": 9.0 + i * per_round_total}}
            for i in range(n)]


def test_roofline_reader_on_a_synthetic_run():
    spec = manifest.layer_metric_spec("moe.expert_matmul_roofline")
    # 2 workers x 8 sequences x 4096 tokens x 8 slots a round, a quarter
    # of them routed here
    total = 2 * 8 * 4096 * 8
    snaps = _snaps(total / 4, total)
    # two traced rounds: 262,144 rows; operations 262,144 * 18 * 2048 *
    # 1024 = 9.896e12 -> 50.23 ms at 197e12/s; bytes 2 * 9 * (262,144 *
    # 3072 + 32 passes * 16 * 2048 * 1024) = 3.382e10 -> 41.29 ms at
    # 819e9/s: the operations bound it
    need = moe_readers.grouped_matmul_need(_cfg(), 262144, 32)
    assert need["flops"] == 262144 * 18 * 2048 * 1024
    assert need["bytes"] == 18 * (262144 * 3072 + 32 * 16 * 2048 * 1024)
    ops = {"ragged-dot-none": 0.04, "ragged-dot-none.7": 0.06,
           "ragged-dot-metadata": 5.0, "fusion.1": 1.0}
    ctx = _ctx(snaps, ops)
    got = moe_readers.expert_matmul_roofline(ctx, spec)
    assert got == pytest.approx(100 * (need["flops"] / 197e12) / 0.1)
    assert 50.0 < got < 50.5
    # few rows: the expert stacks' bytes bound it
    thin = moe_readers.grouped_matmul_need(_cfg(), 1000, 32)
    assert thin["bytes"] / 819e9 > thin["flops"] / 197e12
    assert moe_readers.local_row_share(ctx, {}) == pytest.approx(25.0)
    ms = manifest.layer_metric_spec("moe.expert_matmul_ms")
    assert readers.trace_op_ms_per_round(ctx, ms) == pytest.approx(50.0)


def test_a_program_without_the_counters_or_the_kernels_reports_nothing():
    spec = manifest.layer_metric_spec("moe.expert_matmul_roofline")
    bare = [{"counters": {"van.messages_sent": 8.0 * i}} for i in range(5)]
    ops = {"ragged-dot-none": 0.1}
    assert moe_readers.expert_matmul_roofline(_ctx(bare, ops), spec) is None
    assert moe_readers.local_row_share(_ctx(bare, ops), {}) is None
    assert moe_readers.expert_matmul_roofline(
        _ctx(_snaps(1e4, 4e4), {"fusion.1": 1.0}), spec) is None
    no_trace = _ctx(_snaps(1e4, 4e4), ops)
    no_trace.trace = None
    assert moe_readers.expert_matmul_roofline(no_trace, spec) is None


def test_the_cell_rehearses_from_a_copy_of_the_benchmark(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(manifest.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=manifest.ROOT)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "2147483659", "--seconds", "2", "--trace", "1",
         "--rehearse"], cwd=root, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 10, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] is True and out["correct"] is True, out
    # four of the rehearsal's eight experts are held: about half the rows
    assert 30.0 < out["metrics"]["moe.local_row_share"]["value"] < 70.0
    assert "trainer.compute_ms" in out["metrics"]
