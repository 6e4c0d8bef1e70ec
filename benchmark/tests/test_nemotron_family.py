"""The ``nemotron_h`` family as benchmark data: the configuration against
the catalog's row, its sizes against what ``build`` makes, its manifest
entries, its count of required operations against a hand count, the
readers it names on a synthetic run and on the recorded trace, and the
cell's CPU rehearsal from a copy of the checkout's benchmark files."""

import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest, nemotron_readers, qwen3next_readers, readers

CELL, CONFIG = "nemotron3-ep16-hips-bsc-8k", "nemotron-3-nano-30b-ep16"
CUT = {"num_hidden_layers", "hybrid_override_pattern", "mamba_num_heads",
       "n_groups", "num_attention_heads", "num_key_value_heads",
       "vocab_size", "num_local_experts"}
METRICS = ["nemotron.mamba_ms", "nemotron.ssd_scan_ms",
           "nemotron.ssd_scan_roofline", "nemotron.attn_ms",
           "nemotron.expert_matmul_ms", "nemotron.expert_matmul_roofline",
           "nemotron.dispatch_ms", "nemotron.combine_ms",
           "nemotron.local_row_share"]
PROBE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "v5e_probe.xplane.pb")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
          "blob/main/config.json")


def _cfg():
    return manifest.load_config_file(CONFIG)


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason=f"the catalog {CATALOG} is not on this machine")
def test_published_is_the_catalogs_row():
    """Every key of the row's ``config`` is in ``published`` with the
    row's value."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == SOURCE)
    assert {k: v for k, v in _cfg()["published"].items()
            if k != "num_local_experts"} == row["config"]


def test_published_keys_are_kept_or_named_in_reduced():
    cfg = _cfg()
    assert cfg["source"] == SOURCE
    assert cfg["published"]["num_local_experts"] \
        == cfg["published"]["n_routed_experts"] == 128
    assert cfg["reduced"] == sorted(CUT, key=cfg["reduced"].index)
    assert set(cfg["reduced"]) == CUT
    for key, value in cfg["published"].items():
        assert (cfg[key] == value) != (key in CUT), key


def test_configuration_keeps_every_published_width():
    cfg = _cfg()
    for key, value in {
            "hidden_size": 2688, "mamba_head_dim": 64, "ssm_state_size": 128,
            "conv_kernel": 4, "chunk_size": 128, "expand": 2,
            "moe_intermediate_size": 1856, "intermediate_size": 1856,
            "moe_shared_expert_intermediate_size": 3712,
            "n_routed_experts": 128, "n_shared_experts": 1,
            "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
            "head_dim": 128, "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5,
            "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
            "use_conv_bias": True, "mamba_proj_bias": False,
            "attention_bias": False, "mlp_bias": False,
            "time_step_min": 0.001, "time_step_max": 0.1,
            "time_step_floor": 0.0001, "tie_word_embeddings": False,
            "model_type": "nemotron_h"}.items():
        assert cfg[key] == value, key
    # the share: the published pattern's first segment, rank 0's ranges
    assert cfg["hybrid_override_pattern"] == "MEMEM*"
    assert cfg["published"]["hybrid_override_pattern"].startswith("MEMEM*E")
    pattern = cfg["published"]["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (52, 23, 23, 6)
    assert (cfg["num_hidden_layers"], cfg["published"]["num_hidden_layers"]) \
        == (6, 52)
    assert cfg["local_experts"] == [0, cfg["num_local_experts"]] == [0, 8]
    assert cfg["mamba_heads"] == [0, cfg["mamba_num_heads"]] == [0, 8]
    assert cfg["mamba_groups"] == [0, cfg["n_groups"]] == [0, 1]
    # a rank's heads are whole groups of the published 64 / 8
    assert cfg["mamba_num_heads"] // cfg["n_groups"] \
        == cfg["published"]["mamba_num_heads"] \
        // cfg["published"]["n_groups"] == 8
    assert cfg["query_heads"] == [0, cfg["num_attention_heads"]] == [0, 4]
    assert cfg["key_value_heads"] == [0, cfg["num_key_value_heads"]] == [0, 1]
    assert cfg["vocab_rows"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["microbatch_sequences"] == 1
    assert set(cfg["assumed"]) >= {
        "no_positional_term", "e_score_correction_bias", "router_scoring",
        "experts", "mamba", "W_dt", "initial_values", "initializer_range",
        "auxiliary_loss", "left_out", "microbatch_sequences", "memory_plan"}
    assert "15.5" in cfg["assumed"]["memory_plan"]
    assert set(cfg) >= {"departures", "deployment", "sizes", "rehearsal",
                        "control_dtype", "limits", "limits_read", "precision"}
    assert set(cfg["deployment"]) >= {"stands_for", "this_chip",
                                      "not_imitated"}
    assert any("3 : 2 : 1" in d and "3.8 : 3.8 : 1" in d
               for d in cfg["departures"])
    # both readings that set the limit are in the file, with room
    read = cfg["limits_read"]
    assert 2 * max(read["program_grad_rel_l2_12_seeds"]) \
        <= cfg["limits"]["grad_rel_l2"] \
        <= min(read["control_float8_e4m3fn_3_seeds"]) / 2


def test_sizes_add_up_and_are_what_build_makes():
    cfg = _cfg()
    ref = manifest.family_module("references", cfg["family"])
    mdl = manifest.family_module("models", cfg["family"])
    sizes = cfg["sizes"]
    for group in ("mamba_layer", "expert_layer", "attention_layer"):
        assert sizes[group]["total"] == sum(
            v for k, v in sizes[group].items() if k != "total")
    assert (sizes["mamba_layer"]["total"], sizes["expert_layer"]["total"],
            sizes["attention_layer"]["total"]) \
        == (4_845_464, 100_125_312, 3_443_328)
    assert sizes["parameters"] == 3 * 4_845_464 + 2 * 100_125_312 \
        + 3_443_328 + 2 * 44_040_192 + 2_688 == 306_313_416
    # the program's own tree, by shape alone (nothing is allocated)
    names, _ = mdl.build(cfg, 256)
    shapes = ref.param_shapes(cfg)
    assert sorted(names) == sorted(shapes)
    assert len(names) == sizes["keys"] == 50
    assert ref.num_params(cfg) == sizes["parameters"]
    import jax
    import jax.numpy as jnp
    made = jax.eval_shape(mdl.model_of(cfg).init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 256), jnp.int32))
    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(
        made["params"])) == 306_313_416
    # the bias is a buffer, 128 a sparse layer, and no parameter
    assert sorted(made["buffers"]) == ["block1", "block3"]
    assert sizes["trainer_state_bytes_two_trainers"] == 32 * 306_313_416


def test_manifest_entries():
    man = manifest.load()
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    cfg = _cfg()
    assert entry["source"] == cfg["source"] == SOURCE
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [w["name"] for w in man["workloads"]
            if w["config"] == CONFIG] == [CELL]
    cell = manifest.load_cell(CELL, man)
    assert cell["entry"] == {
        "name": CELL, "config": CONFIG, "traffic": "hips-bsc-8k",
        "chips": 1, "why": cell["spec"]["why"]}
    assert len(cell["entry"]["why"]) <= 200 and len(entry["why"]) <= 200
    # Kanana's cell file to the letter but for name, config, why and the
    # limits read for it
    other = manifest.load_cell("kanana2-ep16-hips-bsc-8k", man)["spec"]
    same = set(other) - {"name", "why", "config", "limits_read"}
    assert {k: cell["spec"][k] for k in same} == {k: other[k] for k in same}
    assert cell["spec"]["data"] == "pattern"
    mine = [m["name"] for m in man["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == sorted(METRICS) == sorted(
        m["name"] for m in man["per_layer"]
        if m["name"].startswith("nemotron."))
    for name in mine:
        spec = manifest.layer_metric_spec(name)
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        for key in ("unit", "layer", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert entry["moves"] == "tokens_per_s_per_chip"
        assert callable(manifest.resolve(spec["reader"]))
    # no other family's metric lists the new cell
    assert CELL not in [c for m in man["per_layer"]
                        if not m["name"].startswith("nemotron.")
                        for c in m.get("workloads", [])]


def test_nemotron_share_hand_count():
    cfg = _cfg()
    ref = manifest.family_module("references", cfg["family"])
    # live score entries at T=8192: a head 8192 * 8193 / 2 = 33,558,528;
    # 4 held query heads in the one * layer: 134,234,112, or 16,386 a
    # token, each 2 * (128 + 128) operations: 8,389,632
    assert ref.live_score_entries(cfg, 8192) == 4 * 33_558_528
    # an M layer: in_proj 2 * 2688 * 1280 = 6,881,280, dt_proj 2 * 2688
    # * 8 = 43,008, the token recurrence 8 heads x 2 products x 2 * 64 *
    # 128 = 262,144, out_proj 2 * 512 * 2688 = 2,752,512: 9,938,944.
    # An E layer: router 2 * 2688 * 128 = 688,128, shared 2 matmuls 4 *
    # 2688 * 3712 = 39,911,424, 6 * 8/128 = 0.375 routed rows a token of
    # 4 * 2688 * 1856 = 19,955,712: 7,483,392; 48,082,944. The * layer's
    # projections 2 * 2688 * 128 * (4 + 4 + 1 + 1) = 6,881,280. Head
    # 2 * 2688 * 16,384 = 88,080,384.
    assert ref.forward_flops_per_token(cfg, 8192) == 3 * 9_938_944 \
        + 2 * 48_082_944 + 6_881_280 + 8_389_632 + 88_080_384 \
        == 229_334_016
    assert ref.train_flops_per_token(cfg, 8192) == 688_002_048
    # the bias: a constant of the file, one vector an expert layer
    bias = ref.correction_bias(cfg)
    assert sorted(bias) == ["block1/e_score_correction_bias",
                            "block3/e_score_correction_bias"]
    assert all(b.shape == (128,) for b in bias.values())
    assert not set(bias) & set(ref.param_shapes(cfg))
    again = ref.correction_bias(cfg)
    assert all((bias[k] == again[k]).all() for k in bias)
    from geomx_tpu.models.transformer import (kernel_score_entries,
                                              score_entries)
    assert score_entries(8192) == (33_558_528, 67_108_864)
    assert kernel_score_entries(8192, 128) >= 33_558_528


def test_the_data_is_windows_of_one_cycle_not_two_token_sets():
    """At the 16,384 rows held ``pattern``'s map has two cycles of
    8,192: a sequence of 8,192 tokens would be one of them whole,
    whatever its first token, and a batch a draw of four from two FIXED
    token sets (PERF.md section 7: what that did to
    ``wan_mb_per_round``). So the file gives the data's range beside
    the rows: ``vocab_size`` 16,363, the largest under the rows at
    which the map is one cycle (and its fixed point): a sequence is a
    window of it at the seed's phase, no token twice, every token with
    a row, and no two fixed sets. The cell's other traffic is Kanana's
    to the letter."""
    import numpy as np

    from benchmark.data import pattern

    cfg = _cfg()
    ref = manifest.family_module("references", cfg["family"])
    rows, span = cfg["vocab_rows"], cfg["vocab_size"]
    seqs = pattern.batch(np.random.default_rng(3), 16, 8192, rows)
    assert all(len(set(seq)) == 8192 for seq in seqs.tolist())
    assert len({frozenset(seq) for seq in seqs.tolist()}) == 2

    def cycles(v):
        nxt, seen, lengths = (3 * np.arange(v) + 7) % v, set(), []
        if len(set(nxt.tolist())) != v:
            return None
        for start in range(v):
            n, x = 0, start
            while x not in seen:
                seen.add(x)
                x, n = int(nxt[x]), n + 1
            if n:
                lengths.append(n)
        return sorted(lengths)

    assert (rows, span) == (16384, 16363)
    assert cycles(span) == [1, span - 1]
    assert all(cycles(v) != [1, v - 1] for v in range(span + 1, rows + 1))
    seqs = pattern.batch(np.random.default_rng(3), 16, 8192, span)
    assert all(len(set(seq)) == 8192 for seq in seqs.tolist())
    assert len({frozenset(seq) for seq in seqs.tolist()}) == 16
    assert int(seqs.max()) < span <= rows
    assert ref.vocab_rows(cfg) == rows
    assert ref.vocab_rows({"vocab_size": 96}) == 96
    assert ref.param_shapes(cfg)["embed/embedding"] == (rows, 2688)
    ours = manifest.load_cell(CELL)["spec"]
    theirs = manifest.load_cell("kanana2-ep16-hips-bsc-8k")["spec"]
    for key in ("data", "batch_per_worker", "seq_len", "batches"):
        assert ours[key] == theirs[key], key


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
ROUND = {"moe.rows_total": 8 * 8192 * 6 * 2,
         "moe.rows_local": 8 * 8192 * 6 * 2 / 16,
         "attn.score_entries_live": 8 * 4 * 33_558_528,
         "attn.score_entries_computed": 8 * 4 * 35_651_584,
         "ssd.head_tokens": 8 * 8192 * 8 * 3, "ssd.chunks": 8 * 3 * 64}


def test_the_named_readers_on_a_synthetic_run(monkeypatch):
    cfg = _cfg()
    ctx = _ctx(_snaps(ROUND), {"ragged-dot-none": 0.05,
                               "ragged-dot-none.7": 0.07,
                               "ragged-dot-metadata": 5.0, "fusion.1": 1.0})
    # the scan, two traced rounds: 2 * 1,572,864 (token, head) pairs of
    # 12 * 64 * 128 = 98,304 operations -> 3.09e11, 1.57 ms at 197e12/s;
    # of 2 * (4 * 64 + 4 + 4 * 128 / 8) = 648 bytes -> 2.04e9, 2.49 ms
    # at 819e9/s: the bytes bound it
    pairs = 2 * 8 * 8192 * 8 * 3
    need = nemotron_readers.ssd_need(cfg, pairs)
    assert need == {"flops": pairs * 98_304.0, "bytes": pairs * 648.0}
    assert need["bytes"] / 819e9 > need["flops"] / 197e12
    spec = manifest.layer_metric_spec("nemotron.ssd_scan_roofline")
    assert spec["scope"] == "ssd_scan"
    monkeypatch.setattr(qwen3next_readers, "scope_ms_per_round",
                        lambda ctx, spec: 150.0)
    got = nemotron_readers.ssd_scan_roofline(ctx, spec)
    assert got == pytest.approx(100 * (need["bytes"] / 819e9) / 0.3)
    assert 0.0 < got < 100.0
    # the experts: 2 rounds x 6,144 x 8 held rows in 2 * 8 passes of
    # two expert layers = 32 calls, two matmuls an expert at width 1856
    rows, calls = 2 * 49152, 2 * 2 * 8
    spec = manifest.layer_metric_spec("nemotron.expert_matmul_roofline")
    assert nemotron_readers.expert_need(cfg, rows, calls) == {
        "flops": rows * 12.0 * 2688 * 1856,
        "bytes": 12.0 * (rows * 4544 + calls * 8 * 2688 * 1856)}
    least = max(rows * 12 * 2688 * 1856 / 197e12,
                12 * (rows * 4544 + calls * 8 * 2688 * 1856) / 819e9)
    got = nemotron_readers.expert_matmul_roofline(ctx, spec)
    assert got == pytest.approx(100 * least / 0.12)
    assert 0.0 < got < 100.0
    share = manifest.layer_metric_spec("nemotron.local_row_share")
    assert manifest.resolve(share["reader"])(ctx, share) == \
        pytest.approx(6.25)
    ms = manifest.layer_metric_spec("nemotron.expert_matmul_ms")
    assert readers.trace_op_ms_per_round(ctx, ms) == pytest.approx(60.0)
    for name, scope in (("nemotron.mamba_ms", "mamba_mixer"),
                        ("nemotron.ssd_scan_ms", "ssd_scan"),
                        ("nemotron.attn_ms", "attention"),
                        ("nemotron.dispatch_ms", "dispatch"),
                        ("nemotron.combine_ms", "combine")):
        spec = manifest.layer_metric_spec(name)
        assert (spec["reader"], spec["scope"]) == (
            "qwen3next_readers:scope_ms_per_round", scope)


def test_a_program_without_scopes_or_counters_reports_nothing(
        tmp_path, monkeypatch):
    """What the new entries read from the parent commit, which has no
    ``nemotron_h`` family, and from another family's trace: nothing,
    without raising. The recorded v5e probe carries none of the
    scopes."""
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
            if name == "nemotron.local_row_share" and \
                    "moe.rows_total" in ctx.snaps[-1]["counters"]:
                continue
            if name == "nemotron.expert_matmul_ms" and ctx.trace and \
                    ctx.trace["op_seconds_first_chip"]:
                continue
            spec = manifest.layer_metric_spec(name)
            assert manifest.resolve(spec["reader"])(ctx, spec) is None, name
    # another family's configuration has none of this family's keys
    other = readers.Context(**dict(
        _ctx(_snaps(ROUND), {"ragged-dot-none": 0.1}).__dict__,
        cfg=manifest.load_config_file("kanana-2-30b-ep16")))
    monkeypatch.setattr(qwen3next_readers, "scope_ms_per_round",
                        lambda ctx, spec: 150.0)
    for name, fn in (("nemotron.expert_matmul_roofline",
                      nemotron_readers.expert_matmul_roofline),
                     ("nemotron.ssd_scan_roofline",
                      nemotron_readers.ssd_scan_roofline)):
        assert fn(other, manifest.layer_metric_spec(name)) is None


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
    # four of the rehearsal's sixteen experts are held: a quarter at
    # even routing
    assert 10.0 < out["metrics"]["nemotron.local_row_share"]["value"] < 60.0
    assert "server.bsc_select_ms" in out["metrics"]
    assert "step.reset_ms" not in out["metrics"]
    assert not [m for m in out["metrics"]
                if m.startswith(("laguna.", "mellum.", "kanana."))]
