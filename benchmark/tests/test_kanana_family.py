"""The ``kanana`` family as benchmark data: the configuration against
the published one, its manifest entries, its count of required
operations against a hand count, the readers it names on a synthetic
run and on the recorded trace, and the cell's CPU rehearsal from a copy
of the checkout's benchmark files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import kanana_readers, manifest, qwen3next_readers, readers

CELL, CONFIG = "kanana2-ep16-hips-bsc-8k", "kanana-2-30b-ep16"
CUT = {"num_hidden_layers", "num_attention_heads", "num_key_value_heads",
       "vocab_size", "num_local_experts"}
METRICS = ["kanana.mla_ms", "kanana.mla_core_ms", "kanana.mla_core_roofline",
           "kanana.mla_live_score_share", "kanana.expert_matmul_ms",
           "kanana.expert_matmul_roofline", "kanana.local_row_share",
           "kanana.dispatch_ms", "kanana.combine_ms"]
PROBE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "v5e_probe.xplane.pb")


def _cfg():
    return manifest.load_config_file(CONFIG)


def test_configuration_keeps_every_published_width():
    cfg = _cfg()
    assert cfg["reduced"] == sorted(CUT, key=cfg["reduced"].index)
    assert set(cfg["reduced"]) == CUT
    for key, value in cfg["published"].items():
        assert (cfg[key] == value) != (key in CUT), key
    for key, value in {
            "hidden_size": 2048, "kv_lora_rank": 512, "q_lora_rank": None,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "qk_head_dim": 192, "v_head_dim": 128, "intermediate_size": 6144,
            "moe_intermediate_size": 768, "n_routed_experts": 128,
            "n_shared_experts": 2, "num_experts_per_tok": 6,
            "routed_scaling_factor": 2.448, "first_k_dense_replace": 1,
            "n_group": 1, "topk_group": 1, "rope_interleave": True,
            "rope_scaling": None, "rope_theta": 1000000,
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "norm_topk_prob": True}.items():
        assert cfg[key] == value, key
    # the share: the dense layer and four behind it, rank 0's ranges
    assert (cfg["num_hidden_layers"], cfg["published"]["num_hidden_layers"]) \
        == (5, 48)
    assert cfg["local_experts"] == [0, cfg["num_local_experts"]] == [0, 8]
    assert cfg["query_heads"] == [0, cfg["num_attention_heads"]] == [0, 4]
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert set(cfg["assumed"]) >= {
        "e_score_correction_bias", "router_scoring", "shared_experts",
        "biases", "q_lora_rank", "head_dim", "rope_layout",
        "auxiliary_loss", "mtp_head", "initializer_range",
        "microbatch_sequences", "memory_plan"}
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
        "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/"
        "blob/main/config.json")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = manifest.load_cell(CELL, man)
    assert cell["entry"] == {
        "name": CELL, "config": CONFIG, "traffic": "hips-bsc-8k",
        "chips": 1, "why": cell["spec"]["why"]}
    assert len(cell["entry"]["why"]) <= 200 and len(entry["why"]) <= 200
    # Mellum2's cell file to the letter but for name, config and why
    other = manifest.load_cell("mellum2-ep8-hips-bsc-8k", man)["spec"]
    same = set(other) - {"name", "why", "config", "limits_read"}
    assert {k: cell["spec"][k] for k in same} == {k: other[k] for k in same}
    mine = [m["name"] for m in man["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == METRICS
    for name in mine:
        spec = manifest.layer_metric_spec(name)
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        for key in ("unit", "layer", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert callable(manifest.resolve(spec["reader"]))
    # the one metric that reads nothing since PR 42 lists the cells it
    # had, so that the new cell does not owe it
    reset = next(m for m in man["per_layer"] if m["name"] == "step.reset_ms")
    assert CELL not in reset["workloads"] and len(reset["workloads"]) == 6
    assert CELL not in [c for m in man["per_layer"]
                        if not m["name"].startswith("kanana.")
                        for c in m.get("workloads", [])]


def test_kanana_share_hand_count():
    cfg = _cfg()
    ref = manifest.family_module("references", cfg["family"])
    # live score entries at T=8192: a head 8192 * 8193 / 2 = 33,558,528;
    # 4 held heads in 5 layers: 671,170,560, or 81,930 a token, each
    # 2 * (192 + 128) operations: 52,435,200
    assert ref.live_score_entries(cfg, 8192) == 20 * 33_558_528
    # a layer's projections: q 2 * 2048 * 768, kv_a 2 * 2048 * 576,
    # kv_b 2 * 512 * 1024, o 2 * 512 * 2048: 8,650,752, 43,253,760 in
    # five. Dense FFN 6 * 2048 * 6144 = 75,497,472. An expert layer:
    # router 2 * 2048 * 128 = 524,288, shared 6 * 2048 * 1536 =
    # 18,874,368, 6 * 8/128 = 0.375 routed rows a token of 6 * 2048 *
    # 768 = 9,437,184: 22,937,600, 91,750,400 in four. Head 2 * 2048 *
    # 16,032 = 65,667,072.
    assert ref.forward_flops_per_token(cfg, 8192) == 43_253_760 \
        + 52_435_200 + 75_497_472 + 91_750_400 + 65_667_072 == 328_603_904
    assert ref.train_flops_per_token(cfg, 8192) == 985_811_712
    assert ref.num_params(cfg) == cfg["sizes"]["parameters"] == 314_860_032
    assert len(ref.param_shapes(cfg)) == cfg["sizes"]["keys"] == 69
    for group in ("a_layers_attention", "dense_layer", "expert_layer"):
        assert cfg["sizes"][group]["total"] == sum(
            v for k, v in cfg["sizes"][group].items() if k != "total")
    # the bias: a constant of the file, one vector a sparse layer, not
    # among the parameters
    bias = ref.correction_bias(cfg)
    assert sorted(bias) == [f"block{i}/e_score_correction_bias"
                            for i in range(1, 5)]
    assert all(b.shape == (128,) for b in bias.values())
    assert not set(bias) & set(ref.param_shapes(cfg))
    again = ref.correction_bias(cfg)
    assert all((bias[k] == again[k]).all() for k in bias)
    # the kernels compute their live blocks of 512 x 512 at a head of 192
    from geomx_tpu.models.transformer import (kernel_score_entries,
                                              score_entries)
    assert score_entries(8192) == (33_558_528, 67_108_864)
    assert kernel_score_entries(8192, 192) == 35_651_584


def _ctx(snaps, op_seconds=None, rounds=2, trace=True):
    return readers.Context(
        cell=CELL, chips=1, peaks=manifest.peaks_for("TPU v5 lite"),
        rounds=4, snaps=snaps,
        trace={"rounds": rounds, "op_seconds_first_chip": op_seconds or {}}
        if trace else None,
        tokens_traced=rounds * 2 * 4 * 8192, reference=None, cfg=_cfg(),
        seq_len=8192)


def _snaps(per_round, n=5):
    return [{"counters": {name: 7.0 + i * value
                          for name, value in per_round.items()}}
            for i in range(n)]


# a round: 2 workers x 4 sequences
ROUND = {"moe.rows_total": 8 * 8192 * 6 * 4,
         "moe.rows_local": 8 * 8192 * 6 * 4 / 16,
         "attn.score_entries_live": 8 * 20 * 33_558_528,
         "attn.score_entries_computed": 8 * 20 * 35_651_584}


def test_the_named_readers_on_a_synthetic_run(monkeypatch):
    cfg = _cfg()
    ctx = _ctx(_snaps(ROUND), {"ragged-dot-none": 0.05,
                               "ragged-dot-none.7": 0.07,
                               "ragged-dot-metadata": 5.0, "fusion.1": 1.0})
    # the core, two traced rounds: 2 * 8 * 20 * 33,558,528 live entries
    # of 6 * 320 operations = 2.062e13 -> 104.7 ms at 197e12/s; 2 * 8 *
    # 8192 * 20 (token, head) pairs of 8 * 320 bytes = 1.342e10 ->
    # 16.4 ms at 819e9/s: the operations bound it
    need = kanana_readers.mla_core_need(cfg, 2 * 8 * 20 * 33_558_528,
                                        2 * 8 * 8192 * 20)
    assert need == {"flops": 2 * 8 * 20 * 33_558_528 * 1920.0,
                    "bytes": 2 * 8 * 8192 * 20 * 2560.0}
    assert need["flops"] / 197e12 > need["bytes"] / 819e9
    spec = manifest.layer_metric_spec("kanana.mla_core_roofline")
    assert spec["scope"] == "latent_core"
    monkeypatch.setattr(qwen3next_readers, "scope_ms_per_round",
                        lambda ctx, spec: 150.0)
    got = kanana_readers.mla_core_roofline(ctx, spec)
    assert got == pytest.approx(100 * (need["flops"] / 197e12) / 0.3)
    assert 0.0 < got < 100.0
    # the experts: 2 rounds x 12,288 x 8 held rows in 2 * 8 passes of
    # four expert layers = 64 calls, at width 768
    rows, calls = 2 * 98304, 4 * 2 * 8
    spec = manifest.layer_metric_spec("kanana.expert_matmul_roofline")
    least = max(rows * 18 * 2048 * 768 / 197e12,
                18 * (rows * 2816 + calls * 8 * 2048 * 768) / 819e9)
    got = kanana_readers.expert_matmul_roofline(ctx, spec)
    assert got == pytest.approx(100 * least / 0.12)
    assert 0.0 < got < 100.0
    share = manifest.layer_metric_spec("kanana.local_row_share")
    assert manifest.resolve(share["reader"])(ctx, share) == \
        pytest.approx(6.25)
    live = manifest.layer_metric_spec("kanana.mla_live_score_share")
    assert manifest.resolve(live["reader"])(ctx, live) == \
        pytest.approx(100 * 33_558_528 / 35_651_584)
    ms = manifest.layer_metric_spec("kanana.expert_matmul_ms")
    assert readers.trace_op_ms_per_round(ctx, ms) == pytest.approx(60.0)


def test_a_program_without_scopes_or_counters_reports_nothing(
        tmp_path, monkeypatch):
    """What the new entries read from the parent commit, which has no
    ``kanana`` family, and from another family's trace: nothing, without
    raising. The recorded v5e probe carries none of the scopes."""
    bare = [{"counters": {"van.messages_sent": 8.0 * i}} for i in range(5)]
    run = tmp_path / "benchmark_out" / "trace" / (CELL + "-7") / "plugins"
    run.mkdir(parents=True)
    shutil.copy(PROBE, run / "host.xplane.pb")
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    # the reader does find the probe's own operations there
    assert qwen3next_readers.scope_ms_per_round(
        _ctx(bare), {"scope": "jit(step)/dot_general"}) > 0
    for ctx in (_ctx(bare, {"ragged-dot-none": 0.1}),
                _ctx(_snaps(ROUND)), _ctx(bare, trace=False)):
        for name in METRICS:
            if name in ("kanana.local_row_share",
                        "kanana.mla_live_score_share") and \
                    "moe.rows_total" in ctx.snaps[-1]["counters"]:
                continue
            if name == "kanana.expert_matmul_ms" and ctx.trace and \
                    ctx.trace["op_seconds_first_chip"]:
                continue
            spec = manifest.layer_metric_spec(name)
            assert manifest.resolve(spec["reader"])(ctx, spec) is None, name
    # another family's configuration has no expert layers to count by
    # this family's keys
    other = readers.Context(**dict(
        _ctx(_snaps(ROUND), {"ragged-dot-none": 0.1}).__dict__,
        cfg=manifest.load_config_file("mellum2-12b-ep8")))
    assert kanana_readers.expert_matmul_roofline(
        other, manifest.layer_metric_spec(
            "kanana.expert_matmul_roofline")) is None


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
    assert 10.0 < out["metrics"]["kanana.local_row_share"]["value"] < 45.0
    # 32 positions: a head keeps 528 of the dense product's 1,024
    assert out["metrics"]["kanana.mla_live_score_share"]["value"] == \
        pytest.approx(100 * 528 / 1024)
    assert "server.bsc_select_ms" in out["metrics"]
    assert "step.reset_ms" not in out["metrics"]
    assert not [m for m in out["metrics"]
                if m.startswith(("laguna.", "mellum."))]
