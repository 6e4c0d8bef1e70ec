"""The ``sdar`` family as benchmark data: the configuration against the
published one, its manifest entries, its count of required operations
against a hand count, the readers it names on a synthetic run and on
the recorded trace, and the cell's CPU rehearsal from a copy of the
checkout's benchmark files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest, qwen3next_readers, readers, sdar_readers

CELL, CONFIG = "sdar-ep16-hips-bsc-4k", "sdar-30b-a3b-ep16"
CUT = {"num_hidden_layers", "num_attention_heads", "num_key_value_heads",
       "vocab_size", "num_local_experts"}
METRICS = ["sdar.attn_ms", "sdar.attn_core_ms", "sdar.attn_core_roofline",
           "sdar.attn_live_score_share", "sdar.masked_position_share",
           "sdar.expert_matmul_ms", "sdar.expert_matmul_roofline",
           "sdar.dispatch_ms", "sdar.combine_ms", "sdar.local_row_share"]
PROBE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "v5e_probe.xplane.pb")


def _cfg():
    return manifest.load_config_file(CONFIG)


def test_configuration_keeps_every_published_width():
    cfg = _cfg()
    assert set(cfg["reduced"]) == CUT
    for key, value in cfg["published"].items():
        assert (cfg[key] == value) != (key in CUT), key
    for key, value in {
            "hidden_size": 2048, "head_dim": 128,
            "moe_intermediate_size": 768, "num_experts": 128,
            "num_experts_per_tok": 8, "norm_topk_prob": True,
            "rope_theta": 1000000, "rope_scaling": None,
            "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
            "model_type": "sdar_moe", "block_length": 4}.items():
        assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["published"]["num_hidden_layers"]) \
        == (4, 48)
    assert cfg["local_experts"] == [0, cfg["num_local_experts"]] == [0, 8]
    assert cfg["query_heads"] == [0, cfg["num_attention_heads"]] == [0, 8]
    assert cfg["key_value_heads"] == [0, cfg["num_key_value_heads"]] == [0, 1]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert set(cfg["assumed"]) >= {
        "block_length", "noise_schedule", "objective", "q_k_norm",
        "mask_token", "training_length", "auxiliary_loss", "mtp_head",
        "router_scoring", "initializer_range", "microbatch_sequences"}
    assert set(cfg) >= {"departures", "deployment", "sizes", "rehearsal",
                        "control_dtype", "limits", "limits_read"}
    assert set(cfg["deployment"]) >= {"stands_for", "this_chip",
                                      "not_imitated"}
    # both readings that set the limit are in the file, with room
    read = cfg["limits_read"]
    assert 2 * max(read["program_grad_rel_l2_12_seeds"]) \
        <= cfg["limits"]["grad_rel_l2"] \
        <= min(read["control_float8_e4m3fn_3_seeds"]) / 2


def test_manifest_entries():
    man = manifest.load()
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    cfg = _cfg()
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
        "config.json")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = manifest.load_cell(CELL, man)
    assert cell["entry"] == {
        "name": CELL, "config": CONFIG, "traffic": "hips-bsc-4k-bd4",
        "chips": 1, "why": cell["spec"]["why"]}
    assert len(cell["entry"]["why"]) <= 200 and len(entry["why"]) <= 200
    # OLMoE's cell file to the letter but for name, config, why and data
    other = manifest.load_cell("olmoe-ep4-hips-bsc", man)["spec"]
    same = set(other) - {"name", "why", "config", "data", "limits_read"}
    assert {k: cell["spec"][k] for k in same} == {k: other[k] for k in same}
    assert cell["spec"]["data"] == "block_noise"
    mine = [m["name"] for m in man["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == METRICS
    for name in mine:
        spec = manifest.layer_metric_spec(name)
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        for key in ("unit", "layer", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert callable(manifest.resolve(spec["reader"]))
    assert CELL not in [c for m in man["per_layer"]
                        if not m["name"].startswith("sdar.")
                        for c in m.get("workloads", [])]


def test_sdar_share_hand_count():
    cfg = _cfg()
    ref = manifest.family_module("references", cfg["family"])
    # live score entries at T=4096, B=4: a head and layer 4096 * 4100 =
    # 16,793,600; 8 held heads in 4 layers
    assert ref.live_score_entries(cfg, 4096) == 32 * 16_793_600
    # a position through a layer: q and o 2 * 2 * 2048 * 1024 =
    # 8,388,608; k and v 2 * 2 * 2048 * 128 = 1,048,576; router 2 * 2048
    # * 128 = 524,288; 8 * 8/128 = 0.5 routed rows of 6 * 2048 * 768 =
    # 9,437,184: 4,718,592. Two positions a counted token through four
    # layers: 8 * 14,680,064 = 117,440,512, less the last layer's clean
    # position's q, o, router and experts, 13,631,488: 103,809,024.
    # Score entries a counted token: 8 heads * 4 layers * 4,100 =
    # 131,200, less the last layer's clean rows' 8 * 2,050 = 16,400, each
    # 4 * 128 operations: 58,777,600. Head once: 2 * 2048 * 18,992 =
    # 77,791,232.
    assert ref.forward_flops_per_token(cfg, 4096) == 103_809_024 \
        + 58_777_600 + 77_791_232 == 240_377_856
    assert ref.train_flops_per_token(cfg, 4096) == 721_133_568
    assert ref.num_params(cfg) == cfg["sizes"]["parameters"] == 248_728_576
    assert len(ref.param_shapes(cfg)) == cfg["sizes"]["keys"] == 51
    assert cfg["sizes"]["a_layer"]["total"] == sum(
        v for k, v in cfg["sizes"]["a_layer"].items() if k != "total")
    # the kernels compute 48 live tiles of 512 x 1,024 a head
    from geomx_tpu.models.transformer import (block_score_entries,
                                              kernel_block_score_entries)
    assert block_score_entries(4096, 4) == (16_793_600, 67_108_864)
    assert kernel_block_score_entries(4096, 4, 128) == 25_165_824


def _ctx(snaps, op_seconds=None, rounds=2, trace=True):
    return readers.Context(
        cell=CELL, chips=1, peaks=manifest.peaks_for("TPU v5 lite"),
        rounds=4, snaps=snaps,
        trace={"rounds": rounds, "op_seconds_first_chip": op_seconds or {}}
        if trace else None,
        tokens_traced=rounds * 2 * 8 * 4096, reference=None, cfg=_cfg(),
        seq_len=4096)


def _snaps(per_round, n=5):
    return [{"counters": {name: 7.0 + i * value
                          for name, value in per_round.items()}}
            for i in range(n)]


# a round: 2 workers x 8 sequences, two positions a token
ROUND = {"moe.rows_total": 16 * 2 * 4096 * 8 * 4,
         "moe.rows_local": 16 * 2 * 4096 * 8 * 4 / 16,
         "attn.score_entries_live": 16 * 32 * 16_793_600,
         "attn.score_entries_computed": 16 * 32 * 25_165_824,
         "diffusion.positions": 16 * 4096,
         "diffusion.positions_masked": 16 * 4096 * 0.49}


def test_the_named_readers_on_a_synthetic_run(monkeypatch):
    cfg = _cfg()
    ctx = _ctx(_snaps(ROUND), {"ragged-dot-none": 0.05,
                               "ragged-dot-none.7": 0.07,
                               "ragged-dot-metadata": 5.0, "fusion.1": 1.0})
    # the core, two traced rounds: 2 * 16 * 32 * 16,793,600 live entries
    # of 6 * 256 operations = 2.641e13 -> 134.1 ms at 197e12/s; 2 * 16 *
    # 8,192 * 32 (position, head) pairs of 16 * 128 bytes = 1.718e10 ->
    # 21.0 ms at 819e9/s: the operations bound it
    need = sdar_readers.blockdiff_core_need(
        cfg, 2 * 16 * 32 * 16_793_600, 2 * 16 * 8192 * 32)
    assert need == {"flops": 2 * 16 * 32 * 16_793_600 * 1536.0,
                    "bytes": 2 * 16 * 8192 * 32 * 2048.0}
    assert need["flops"] / 197e12 > need["bytes"] / 819e9
    spec = manifest.layer_metric_spec("sdar.attn_core_roofline")
    assert spec["scope"] == "blockdiff_core"
    monkeypatch.setattr(qwen3next_readers, "scope_ms_per_round",
                        lambda ctx, spec: 400.0)
    got = sdar_readers.attn_core_roofline(ctx, spec)
    assert got == pytest.approx(100 * (need["flops"] / 197e12) / 0.8)
    assert 0.0 < got < 100.0
    # the experts: 2 rounds x 16 passes x 4 layers x 4,096 held rows in
    # 2 * 16 * 4 = 128 calls, at width 768
    rows, calls = 2 * 16 * 4 * 4096, 2 * 16 * 4
    spec = manifest.layer_metric_spec("sdar.expert_matmul_roofline")
    least = max(rows * 18 * 2048 * 768 / 197e12,
                18 * (rows * 2816 + calls * 8 * 2048 * 768) / 819e9)
    got = sdar_readers.expert_matmul_roofline(ctx, spec)
    assert got == pytest.approx(100 * least / 0.12)
    assert 0.0 < got < 100.0
    for name, want in (("sdar.local_row_share", 6.25),
                       ("sdar.masked_position_share", 49.0),
                       ("sdar.attn_live_score_share",
                        100 * 16_793_600 / 25_165_824)):
        spec = manifest.layer_metric_spec(name)
        assert manifest.resolve(spec["reader"])(ctx, spec) == \
            pytest.approx(want), name
    ms = manifest.layer_metric_spec("sdar.expert_matmul_ms")
    assert readers.trace_op_ms_per_round(ctx, ms) == pytest.approx(60.0)


def test_a_program_without_scopes_or_counters_reports_nothing(
        tmp_path, monkeypatch):
    """What the new entries read from the parent commit, which has no
    ``sdar`` family, and from another family's trace: nothing, without
    raising. The recorded v5e probe carries none of the scopes."""
    bare = [{"counters": {"van.messages_sent": 8.0 * i}} for i in range(5)]
    run = tmp_path / "benchmark_out" / "trace" / (CELL + "-7") / "plugins"
    run.mkdir(parents=True)
    shutil.copy(PROBE, run / "host.xplane.pb")
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    assert qwen3next_readers.scope_ms_per_round(
        _ctx(bare), {"scope": "jit(step)/dot_general"}) > 0
    by_counter = ("sdar.local_row_share", "sdar.attn_live_score_share",
                  "sdar.masked_position_share")
    for ctx in (_ctx(bare, {"ragged-dot-none": 0.1}),
                _ctx(_snaps(ROUND)), _ctx(bare, trace=False)):
        for name in METRICS:
            if name in by_counter and \
                    "moe.rows_total" in ctx.snaps[-1]["counters"]:
                continue
            if name == "sdar.expert_matmul_ms" and ctx.trace and \
                    ctx.trace["op_seconds_first_chip"]:
                continue
            spec = manifest.layer_metric_spec(name)
            assert manifest.resolve(spec["reader"])(ctx, spec) is None, name
    # another family's configuration is not read by this family's keys
    other = readers.Context(**dict(
        _ctx(_snaps(ROUND), {"ragged-dot-none": 0.1}).__dict__,
        cfg=manifest.load_config_file("mellum2-12b-ep8")))
    for name in ("sdar.expert_matmul_roofline", "sdar.attn_core_roofline"):
        spec = manifest.layer_metric_spec(name)
        assert manifest.resolve(spec["reader"])(other, spec) is None, name


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
    assert 10.0 < out["metrics"]["sdar.local_row_share"]["value"] < 45.0
    # 32 tokens in blocks of 4: a head keeps 32 * 36 of the dense
    # product's 64 * 64
    assert out["metrics"]["sdar.attn_live_score_share"]["value"] == \
        pytest.approx(100 * 1152 / 4096)
    assert 25.0 < out["metrics"]["sdar.masked_position_share"]["value"] < 75.0
    assert "server.bsc_select_ms" in out["metrics"]
    assert not [m for m in out["metrics"]
                if m.startswith(("laguna.", "mellum.", "kanana."))]
