"""The ``mellum`` family as benchmark data: the configuration against
the published one, its manifest entries, its count of required
operations against a hand count, the readers it names on a synthetic
run, and the cell's CPU rehearsal from a copy of the checkout's
benchmark files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import laguna_readers, manifest, readers

CELL, CONFIG = "mellum2-ep8-hips-bsc-8k", "mellum2-12b-ep8"
CUT = {"num_hidden_layers", "layer_types", "mlp_layer_types",
       "num_attention_heads", "num_key_value_heads", "vocab_size",
       "num_local_experts"}
METRICS = ["mellum.attn_window_ms", "mellum.attn_full_ms",
           "mellum.attn_live_score_share", "mellum.expert_matmul_ms",
           "mellum.expert_matmul_roofline", "mellum.local_row_share",
           "mellum.dispatch_ms", "mellum.combine_ms"]


def _cfg():
    return manifest.load_config_file(CONFIG)


def test_configuration_keeps_every_published_width():
    cfg = _cfg()
    assert set(cfg["reduced"]) == CUT
    for key, value in cfg["published"].items():
        assert (cfg[key] == value) != (key in CUT), key
    for key, value in {
            "hidden_size": 2304, "head_dim": 128, "intermediate_size": 7168,
            "moe_intermediate_size": 896, "num_experts": 64,
            "num_experts_per_tok": 8, "sliding_window": 1024,
            "norm_topk_prob": True}.items():
        assert cfg[key] == value, key
    assert cfg["rope_parameters"]["full_attention"]["factor"] == 16
    assert "partial_rotary_factor" not in json.dumps(cfg["rope_parameters"])
    # the share: one whole period, every layer sparse, rank 0's ranges
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:4] == \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["mlp_layer_types"] == ["sparse"] * 4
    assert cfg["local_experts"] == [0, cfg["num_local_experts"]] == [0, 8]
    assert cfg["key_value_heads"] == [0, cfg["num_key_value_heads"]] == [0, 1]
    assert cfg["query_heads"] == [0, cfg["num_attention_heads"]] == [0, 8]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert set(cfg["assumed"]) >= {
        "q_k_norm", "biases", "shared_expert", "router_scoring",
        "expert_groups_and_correction_bias", "auxiliary_loss", "rope_layout",
        "yarn", "sliding_window", "mtp_head", "initializer_range",
        "microbatch_sequences"}
    assert set(cfg) >= {"departures", "deployment", "sizes", "rehearsal",
                        "control_dtype", "limits", "limits_read"}
    assert set(cfg["deployment"]) == {"stands_for", "this_chip",
                                      "not_imitated"}


def test_manifest_entries():
    man = manifest.load()
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    cfg = _cfg()
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
        "blob/main/config.json")
    assert entry["reduced"] == cfg["reduced"]
    cell = manifest.load_cell(CELL, man)
    assert cell["entry"] == {
        "name": CELL, "config": CONFIG, "traffic": "hips-bsc-8k",
        "chips": 1, "why": cell["spec"]["why"]}
    assert len(cell["entry"]["why"]) <= 200
    # the Laguna cell's traffic (lr 0.05 with it) but for the shape of a
    # round's tokens: the same 65,536 a round, in sequences twice as long
    other = manifest.load_cell("laguna-ep32-hips-bsc", man)["spec"]
    same = set(other) - {"name", "why", "config", "limits_read",
                         "batch_per_worker", "seq_len"}
    assert cell["spec"]["lr"] == 0.05
    assert {k: cell["spec"][k] for k in same} == {k: other[k] for k in same}
    assert (cell["spec"]["batch_per_worker"], cell["spec"]["seq_len"]) == \
        (4, 8192)
    assert cell["spec"]["batch_per_worker"] * cell["spec"]["seq_len"] == \
        other["batch_per_worker"] * other["seq_len"]
    mine = [m["name"] for m in man["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == METRICS
    for name in mine:
        spec = manifest.layer_metric_spec(name)
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        for key in ("unit", "layer", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert callable(manifest.resolve(spec["reader"]))


def test_mellum_share_hand_count():
    cfg = _cfg()
    ref = manifest.family_module("references", cfg["family"])
    # live score entries at T=8192: a full head 8192 * 8193 / 2 =
    # 33,558,528, a sliding head 1024 * 1025 / 2 + 7168 * 1024 =
    # 7,864,832 (23.4% of a full one); 8 held query heads in three
    # sliding layers and one full: 8 * 57,153,024 = 457,224,192, or
    # 55,813.5 a token, 4 * 128 operations each: 28,576,512
    assert ref.live_score_entries(cfg, 8192) == 457_224_192
    # a layer: q, o over 8 query heads and k, v over the one key/value
    # head, 2 * 2304 * 128 = 589,824 a head and projection, 18 of them:
    # 10,616,832; router 2 * 2304 * 64 = 294,912; 8 * 8/64 = 1 routed
    # row a token of 6 * 2304 * 896 = 12,386,304: 23,298,048 a layer,
    # 93,192,192 in four. Head 2 * 2304 * 12,288 = 56,623,104.
    assert ref.forward_flops_per_token(cfg, 8192) == 178_391_808
    assert ref.train_flops_per_token(cfg, 8192) == 535_175_424
    assert ref.num_params(cfg) == cfg["sizes"]["parameters"] == 276_648_192
    assert len(ref.param_shapes(cfg)) == cfg["sizes"]["keys"] == 43
    assert cfg["sizes"]["a_layer"]["total"] == 55_005_696 == sum(
        v for k, v in cfg["sizes"]["a_layer"].items() if k != "total")
    # the program computes what its score products have by shape: 8
    # blocks of 1,024 queries against 2,048 key columns in a sliding
    # head, the kernel's live blocks in a full one
    from geomx_tpu.models.transformer import (kernel_score_entries,
                                              score_entries)
    assert score_entries(8192) == (33_558_528, 67_108_864)
    assert score_entries(8192, 1024) == (7_864_832, 16_777_216)
    live, covered = 3 * 7_864_832 + 33_558_528, \
        3 * 16_777_216 + kernel_score_entries(8192, 128)
    assert 33_558_528 < kernel_score_entries(8192, 128) < 67_108_864
    assert 0.6 < live / covered < 0.7


def _ctx(snaps, op_seconds, rounds=2):
    return readers.Context(
        cell=CELL, chips=1, peaks=manifest.peaks_for("TPU v5 lite"),
        rounds=4, snaps=snaps,
        trace={"rounds": rounds, "op_seconds_first_chip": op_seconds},
        tokens_traced=rounds * 2 * 4 * 8192, reference=None, cfg=_cfg(),
        seq_len=8192)


def _snaps(per_round, n=5):
    return [{"counters": {name: 7.0 + i * value
                          for name, value in per_round.items()}}
            for i in range(n)]


# a round: 2 workers x 4 sequences
ROUND = {"moe.rows_total": 8 * 8192 * 8 * 4,
         "moe.rows_local": 8 * 8192 * 8 * 4 / 8,
         "attn.score_entries_live": 8 * 457_224_192,
         "attn.score_entries_computed": 8 * 8 * 88_080_384}


def test_the_named_readers_on_a_synthetic_run():
    spec = manifest.layer_metric_spec("mellum.expert_matmul_roofline")
    cfg = _cfg()
    assert cfg["microbatch_sequences"] == 1
    # two traced rounds: 524,288 held rows in 2 * 8 passes of four
    # expert layers = 64 calls. Operations 524,288 * 18 * 2304 * 896 =
    # 1.948e13 -> 98.9 ms at 197e12/s. Bytes 18 * (524,288 * 3200 + 64 *
    # 8 * 2304 * 896) = 4.92e10 -> 60.1 ms at 819e9/s: at 8,192 rows a
    # pass the operations bound it, unlike Laguna's 1,024
    calls = 4 * 2 * 8
    need = laguna_readers.grouped_matmul_need(cfg, 524288, calls)
    assert need["flops"] == 524288 * 18 * 2304 * 896
    assert need["bytes"] == 18 * (524288 * 3200 + calls * 8 * 2304 * 896)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9
    ops = {"ragged-dot-none": 0.1, "ragged-dot-none.7": 0.15,
           "ragged-dot-metadata": 5.0, "fusion.1": 1.0}
    ctx = _ctx(_snaps(ROUND), ops)
    got = laguna_readers.expert_matmul_roofline(ctx, spec)
    assert got == pytest.approx(100 * (need["flops"] / 197e12) / 0.25)
    assert 0.0 < got < 100.0
    share = manifest.layer_metric_spec("mellum.local_row_share")
    assert manifest.resolve(share["reader"])(ctx, share) == \
        pytest.approx(12.5)
    live = manifest.layer_metric_spec("mellum.attn_live_score_share")
    assert manifest.resolve(live["reader"])(ctx, live) == \
        pytest.approx(100 * 57_153_024 / 88_080_384)
    ms = manifest.layer_metric_spec("mellum.expert_matmul_ms")
    assert readers.trace_op_ms_per_round(ctx, ms) == pytest.approx(125.0)


def test_a_program_without_the_counters_reports_nothing():
    """What the new entries read from the parent commit, which has no
    ``mellum`` family: nothing, without raising."""
    bare = [{"counters": {"van.messages_sent": 8.0 * i}} for i in range(5)]
    ops = {"ragged-dot-none": 0.1}
    for name in ("mellum.expert_matmul_roofline",
                 "mellum.attn_live_score_share", "mellum.local_row_share"):
        spec = manifest.layer_metric_spec(name)
        assert manifest.resolve(spec["reader"])(_ctx(bare, ops), spec) \
            is None, name


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
    assert 10.0 < out["metrics"]["mellum.local_row_share"]["value"] < 45.0
    # 32 positions under a window of 8: a sliding head keeps 228 of 512
    # entries, a full head 528 of 1,024: (3 * 228 + 528) / (3 * 512 +
    # 1024)
    assert out["metrics"]["mellum.attn_live_score_share"]["value"] == \
        pytest.approx(100 * 1212 / 2560)
    assert "server.bsc_select_ms" in out["metrics"]
    assert not [m for m in out["metrics"] if m.startswith("laguna.")]
