"""The ``laguna`` family as benchmark data: the configuration against
the published one, its manifest entries, its count of required
operations against a hand count, the readers on a synthetic run, and
the cell's CPU rehearsal from a copy of the checkout's benchmark files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import laguna_readers, manifest, moe_readers, readers

CELL, CONFIG = "laguna-ep32-hips-bsc", "laguna-xs2-ep32"
CUT = {"num_hidden_layers", "layer_types", "mlp_layer_types",
       "num_attention_heads_per_layer", "num_attention_heads",
       "num_key_value_heads", "vocab_size", "num_local_experts"}


def _cfg():
    return manifest.load_config_file(CONFIG)


def test_configuration_keeps_every_published_width():
    cfg = _cfg()
    assert set(cfg["reduced"]) == CUT
    for key, value in cfg["published"].items():
        assert (cfg[key] == value) != (key in CUT), key
    for key, value in {
            "hidden_size": 2048, "head_dim": 128, "intermediate_size": 8192,
            "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512, "num_experts": 256,
            "num_experts_per_tok": 8, "sliding_window": 512,
            "moe_routed_scaling_factor": 2.5}.items():
        assert cfg[key] == value, key
    # the share: the dense layer and the period that follows
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:5]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["local_experts"] == [0, cfg["num_local_experts"]]
    assert cfg["key_value_heads"] == [0, cfg["num_key_value_heads"]]
    assert [hi - lo for lo, hi in cfg["query_heads"]] == \
        cfg["num_attention_heads_per_layer"] == \
        [h // 8 for h in cfg["published"]["num_attention_heads_per_layer"][:5]]
    assert set(cfg["assumed"]) >= {
        "gating", "router_scoring", "q_k_norm", "biases",
        "expert_groups_and_correction_bias", "auxiliary_loss",
        "initializer_range"}


def test_manifest_entries():
    man = manifest.load()
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    cfg = _cfg()
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")
    assert entry["reduced"] == cfg["reduced"]
    cell = manifest.load_cell(CELL, man)
    assert cell["entry"] == {
        "name": CELL, "config": CONFIG, "traffic": "hips-bsc-4k",
        "chips": 1, "why": cell["spec"]["why"]}
    assert len(cell["entry"]["why"]) <= 200
    # the OLMoE cell's traffic, to the letter
    other = manifest.load_cell("olmoe-ep4-hips-bsc", man)["spec"]
    same = set(other) - {"name", "why", "config", "limits_read"}
    assert {k: cell["spec"][k] for k in same} == {k: other[k] for k in same}
    mine = [m["name"] for m in man["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["laguna.expert_matmul_ms",
                    "laguna.expert_matmul_roofline",
                    "laguna.local_row_share",
                    "laguna.attn_live_score_share"]
    for name in mine:
        spec = manifest.layer_metric_spec(name)
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        for key in ("unit", "layer", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert callable(manifest.resolve(spec["reader"]))


def test_laguna_share_hand_count():
    cfg = _cfg()
    ref = manifest.family_module("references", cfg["family"])
    # live score entries at T=4096: a full head 4096 * 4097 / 2 =
    # 8,390,656, a sliding head 512 * 513 / 2 + 3584 * 512 = 1,966,336;
    # 2 x 6 full and 3 x 8 sliding heads held: 147,879,936, or 36,103.5
    # a token, 4 * 128 operations each: 18,484,992
    assert ref.live_score_entries(cfg, 4096) == 147_879_936
    # projections: q, gate, o over the held query heads and k, v over
    # the one key/value head, 2 * 2048 * 128 = 524,288 a head: 20 heads'
    # worth in a full layer, 26 in a sliding one: 2 * 10,485,760 + 3 *
    # 13,631,488 = 61,865,984. Dense FFN 6 * 2048 * 8192 = 100,663,296.
    # A sparse layer: router 2 * 2048 * 256 = 1,048,576, shared expert
    # 6 * 2048 * 512 = 6,291,456, routed 8 * 8/256 = 0.25 rows a token
    # of 6,291,456: 8,912,896, four of them 35,651,584. Head 2 * 2048 *
    # 12,544 = 51,380,224.
    assert ref.forward_flops_per_token(cfg, 4096) == 268_046_080
    assert ref.train_flops_per_token(cfg, 4096) == 804_138_240
    assert ref.num_params(cfg) == cfg["sizes"]["parameters"] == 248_010_752
    assert len(ref.param_shapes(cfg)) == cfg["sizes"]["keys"] == 69
    # the program computes what its score products have by shape
    from geomx_tpu.models.transformer import score_entries
    assert score_entries(4096) == (8_390_656, 16_777_216)
    assert score_entries(4096, 512) == (1_966_336, 4_194_304)


def _ctx(snaps, op_seconds, rounds=2):
    return readers.Context(
        cell=CELL, chips=1, peaks=manifest.peaks_for("TPU v5 lite"),
        rounds=4, timed=[], snaps=snaps,
        trace={"rounds": rounds, "op_seconds_first_chip": op_seconds},
        tokens_traced=rounds * 2 * 8 * 4096, reference=None, cfg=_cfg(),
        seq_len=4096)


def _snaps(per_round, n=5):
    return [{"counters": {name: 7.0 + i * value
                          for name, value in per_round.items()}}
            for i in range(n)]


# a round: 2 workers x 8 sequences
ROUND = {"moe.rows_total": 16 * 4096 * 8 * 4,
         "moe.rows_local": 16 * 4096 * 8 * 4 / 32,
         "attn.score_entries_live": 16 * 147_879_936,
         "attn.score_entries_computed": 16 * (12 * 16_777_216
                                              + 24 * 4_194_304)}


def test_readers_on_a_synthetic_run():
    spec = manifest.layer_metric_spec("laguna.expert_matmul_roofline")
    cfg = _cfg()
    micro = cfg["microbatch_sequences"]
    # two traced rounds: 131,072 rows in 2 * 16 / micro passes of four
    # expert layers. Operations 131,072 * 18 * 2048 * 512 = 2.474e12 ->
    # 12.56 ms at 197e12/s. Bytes 18 * (131,072 * 2560 + passes * 8 *
    # 2048 * 512): with 128 passes 2.537e10 -> 30.97 ms at 819e9/s: the
    # expert stacks' bytes bound it
    calls = 4 * 32 / micro
    need = laguna_readers.grouped_matmul_need(cfg, 131072, calls)
    assert need["flops"] == 131072 * 18 * 2048 * 512
    assert need["bytes"] == 18 * (131072 * 2560 + calls * 8 * 2048 * 512)
    assert need["bytes"] / 819e9 > need["flops"] / 197e12
    # the expert width, not the dense layer's: moe_readers would count
    # intermediate_size and every layer
    assert moe_readers.grouped_matmul_need(cfg, 131072, calls)["flops"] \
        == 16 * need["flops"]
    ops = {"ragged-dot-none": 0.04, "ragged-dot-none.7": 0.06,
           "ragged-dot-metadata": 5.0, "fusion.1": 1.0}
    ctx = _ctx(_snaps(ROUND), ops)
    got = laguna_readers.expert_matmul_roofline(ctx, spec)
    assert got == pytest.approx(100 * (need["bytes"] / 819e9) / 0.1)
    assert 0.0 < got < 100.0
    share = manifest.layer_metric_spec("laguna.local_row_share")
    assert manifest.resolve(share["reader"])(ctx, share) == \
        pytest.approx(3.125)
    live = manifest.layer_metric_spec("laguna.attn_live_score_share")
    assert manifest.resolve(live["reader"])(ctx, live) == \
        pytest.approx(100 * 147_879_936 / 301_989_888)
    ms = manifest.layer_metric_spec("laguna.expert_matmul_ms")
    assert readers.trace_op_ms_per_round(ctx, ms) == pytest.approx(50.0)


def test_a_program_without_the_counters_or_the_kernels_reports_nothing():
    spec = manifest.layer_metric_spec("laguna.expert_matmul_roofline")
    bare = [{"counters": {"van.messages_sent": 8.0 * i}} for i in range(5)]
    ops = {"ragged-dot-none": 0.1}
    assert laguna_readers.expert_matmul_roofline(_ctx(bare, ops), spec) \
        is None
    assert laguna_readers.attn_live_score_share(_ctx(bare, ops), {}) is None
    assert laguna_readers.expert_matmul_roofline(
        _ctx(_snaps(ROUND), {"fusion.1": 1.0}), spec) is None
    no_trace = _ctx(_snaps(ROUND), ops)
    no_trace.trace = None
    assert laguna_readers.expert_matmul_roofline(no_trace, spec) is None


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
    # four of the rehearsal's sixteen experts are held: about a quarter
    assert 10.0 < out["metrics"]["laguna.local_row_share"]["value"] < 45.0
    # 32 positions under a window of 8: a full head keeps 528 of 1,024
    # entries, a sliding head 228 of 512: (6 * 528 + 12 * 228) / (6 *
    # 1024 + 12 * 512)
    assert out["metrics"]["laguna.attn_live_score_share"]["value"] == \
        pytest.approx(100 * 5904 / 12288)
    assert "trainer.compute_ms" in out["metrics"]
    assert not [m for m in out["metrics"] if m.startswith("moe.")]
