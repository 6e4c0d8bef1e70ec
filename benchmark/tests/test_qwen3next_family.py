"""The ``qwen3next`` family as benchmark data: the configuration against
the published one, its manifest entries, its counts of required
operations and bytes against hand counts, the readers on a synthetic
run and on the recorded trace, and the cell's CPU rehearsal from a copy
of the checkout's benchmark files."""

import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest, qwen3next_readers, readers, trace_reduce

CELL, CONFIG = "qwen3next-ep64-hips-bsc", "qwen3-next-80b-ep64"
CUT = {"num_hidden_layers", "num_attention_heads", "num_key_value_heads",
       "linear_num_key_heads", "linear_num_value_heads", "vocab_size",
       "num_local_experts"}
PROBE = os.path.join(os.path.dirname(__file__), "data",
                     "v5e_probe.xplane.pb")


def _cfg():
    return manifest.load_config_file(CONFIG)


def test_configuration_keeps_every_published_width():
    cfg = _cfg()
    assert set(cfg["reduced"]) == CUT
    for key, value in cfg["published"].items():
        assert (cfg[key] == value) != (key in CUT), key
    for key, value in {
            "hidden_size": 2048, "head_dim": 256, "linear_key_head_dim": 128,
            "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
            "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512, "num_experts": 512,
            "num_experts_per_tok": 10, "partial_rotary_factor": 0.25,
            "rope_theta": 10000000, "full_attention_interval": 4}.items():
        assert cfg[key] == value, key
    # the share: one whole period, half the heads, 1/64 of the experts,
    # 1/8 of the rows
    assert cfg["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) \
        == cfg["full_attention_interval"]
    pub = cfg["published"]
    assert cfg["local_experts"] == [0, cfg["num_local_experts"]] == [0, 8]
    assert cfg["key_value_heads"] == [0, cfg["num_key_value_heads"]]
    assert cfg["query_heads"] == [0, cfg["num_attention_heads"]]
    assert cfg["linear_key_heads_held"] == [0, cfg["linear_num_key_heads"]]
    assert cfg["linear_value_heads_held"] == \
        [0, cfg["linear_num_value_heads"]]
    for key in ("num_attention_heads", "num_key_value_heads",
                "linear_num_key_heads", "linear_num_value_heads"):
        assert cfg[key] * 2 == pub[key], key
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert cfg["num_local_experts"] * 64 == pub["num_experts"]
    assert set(cfg["assumed"]) >= {
        "norm_form", "layer_pattern", "projection_layout", "convolution",
        "decay_init", "router_scoring", "shared_expert", "auxiliary_loss",
        "initializer_range"}
    assert any("multi-token-prediction" in d for d in cfg["departures"])


def test_manifest_entries():
    man = manifest.load()
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    cfg = _cfg()
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    assert entry["reduced"] == cfg["reduced"]
    assert len(entry["why"]) <= 200
    cell = manifest.load_cell(CELL, man)
    assert cell["entry"] == {
        "name": CELL, "config": CONFIG, "traffic": "hips-bsc-4k",
        "chips": 1, "why": cell["spec"]["why"]}
    assert len(cell["entry"]["why"]) <= 200
    # the OLMoE and Laguna cells' traffic, to the letter
    for twin in ("olmoe-ep4-hips-bsc", "laguna-ep32-hips-bsc"):
        other = manifest.load_cell(twin, man)["spec"]
        same = set(other) - {"name", "why", "config", "limits_read"}
        assert {k: cell["spec"][k] for k in same} == \
            {k: other[k] for k in same}, twin
    mine = [m["name"] for m in man["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["qwen3next.gdn_scan_ms", "qwen3next.gdn_scan_roofline",
                    "qwen3next.linear_layer_ms", "qwen3next.local_row_share"]
    for name in mine:
        spec = manifest.layer_metric_spec(name)
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        for key in ("unit", "layer", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert callable(manifest.resolve(spec["reader"]))
    # the cell reports every metric that lists no cells, and no other
    # family's
    reported = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert "step.busy_mfu" in reported and set(mine) <= reported
    assert not [n for n in reported if n.startswith(("laguna.", "moe."))]


def test_share_hand_count():
    cfg = _cfg()
    ref = manifest.family_module("references", cfg["family"])
    sizes = cfg["sizes"]
    shapes = ref.param_shapes(cfg)
    assert ref.num_params(cfg) == sizes["parameters"] == 259_468_256
    assert len(shapes) == sizes["keys"] == 70
    assert sizes["trainer_state_bytes_two_trainers"] == 32 * 259_468_256

    def part(prefix):
        return sum(math.prod(s) for n, s in shapes.items()
                   if n.startswith(prefix))

    assert part("block0/linear_attn/") == \
        sizes["linear_layer_mixer"]["total"] == 16_859_296
    assert sum(part(f"block3/{n}") for n in (
        "q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm")) == \
        sizes["full_layer_mixer"]["total"] == 13_632_000
    assert part("block3/") - 13_632_000 == \
        sizes["every_layer_moe_and_norms"]["total"] == 29_366_272
    assert part("block") == sizes["four_layers"] == 181_674_976
    # the smallest keys: 16-element decay vectors, selected one a round
    assert shapes["block0/linear_attn/A_log"] == (16,)
    # live score entries at T=4096: 8 query heads in the one full layer,
    # 4096 * 4097 / 2 = 8,390,656 each; 4 * 256 operations an entry and
    # 16,388 entries a token
    assert ref.live_score_entries(cfg, 4096) == 67_125_248
    # a linear layer, a token: in_proj_qkvz 2 * 2048 * 6144 = 25,165,824,
    # in_proj_ba 2 * 2048 * 32 = 131,072, out_proj 2 * 2048 * 2048 =
    # 8,388,608, the recurrence 16 heads * 3 * 2 * 128 * 128 = 1,572,864:
    # 35,258,368. The full layer: q_proj, o_proj and k, v, 2 * 2048 * 256
    # = 1,048,576 a head's worth, 3 * 8 + 2 of them: 27,262,976; scores
    # 4 * 256 * 16,388 = 16,781,312. Every layer: router 2 * 2048 * 512
    # = 2,097,152, shared expert 6 * 2048 * 512 = 6,291,456, routed 10 *
    # 8 / 512 = 0.15625 rows of 6,291,456 = 983,040: 9,371,648. Head 2 *
    # 2048 * 18,992 = 77,791,232.
    assert ref.forward_flops_per_token(cfg, 4096) == (
        3 * 35_258_368 + 27_262_976 + 16_781_312 + 4 * 9_371_648
        + 77_791_232) == 265_097_216
    assert ref.train_flops_per_token(cfg, 4096) == 795_291_648


def _ctx(snaps, rounds=2, trace=True):
    return readers.Context(
        cell=CELL, chips=1, peaks=manifest.peaks_for("TPU v5 lite"),
        rounds=4, timed=[], snaps=snaps,
        trace={"rounds": rounds} if trace else None,
        tokens_traced=rounds * 2 * 8 * 4096, reference=None, cfg=_cfg(),
        seq_len=4096)


def _snaps(per_round, n=5):
    return [{"counters": {name: 7.0 + i * value
                          for name, value in per_round.items()}}
            for i in range(n)]


# a round: 2 workers x 8 sequences
ROUND = {"moe.rows_total": 16 * 4096 * 10 * 4,
         "moe.rows_local": 16 * 4096 * 10 * 4 / 64,
         "gdn.head_tokens": 16 * 4096 * 16 * 3, "gdn.chunks": 16 * 3 * 64}


def test_gdn_need_hand_count():
    # a (token, value head) pair: 3 products of 2 * 128 * 128 forward and
    # twice that backward, 18 * 16,384 = 294,912 operations; q and k a
    # key head serve two value heads: 2 * 128 / 2 + 2 * 128 = 384
    # two-byte elements and two float32 scalars, 776 bytes, and their
    # gradients as many: 1,552
    need = qwen3next_readers.gdn_need(_cfg(), 1000)
    assert need == {"flops": 294_912_000.0, "bytes": 1_552_000.0}
    # the bytes bound it on a v5e: 1.90 ns against 1.50 ns a pair
    assert need["bytes"] / 819e9 > need["flops"] / 197e12


def test_readers_on_a_synthetic_run(monkeypatch):
    ctx = _ctx(_snaps(ROUND))
    share = manifest.layer_metric_spec("qwen3next.local_row_share")
    assert manifest.resolve(share["reader"])(ctx, share) == \
        pytest.approx(1.5625)
    # two traced rounds with 400 ms a round under the scope: 6,291,456
    # pairs need 6,291,456 * 1,552 / 819e9 = 11.92 ms of 800
    spec = manifest.layer_metric_spec("qwen3next.gdn_scan_roofline")
    monkeypatch.setattr(qwen3next_readers, "scope_ms_per_round",
                        lambda ctx, spec: 400.0)
    got = qwen3next_readers.gdn_scan_roofline(ctx, spec)
    assert got == pytest.approx(100 * 6_291_456 * 1552 / 819e9 / 0.8)
    assert 0.0 < got < 100.0
    # no counter (a parent's program), no peaks: nothing
    bare = [{"counters": {"van.messages_sent": 8.0 * i}} for i in range(5)]
    assert qwen3next_readers.gdn_scan_roofline(_ctx(bare), spec) is None
    monkeypatch.setattr(qwen3next_readers, "scope_ms_per_round",
                        lambda ctx, spec: None)
    assert qwen3next_readers.gdn_scan_roofline(ctx, spec) is None


def test_scope_reader_on_the_recorded_trace(tmp_path, monkeypatch):
    """The recorded v5e probe has three ``convert_reduce_fusion``
    operations under ``jit(step)/dot_general`` and nothing under the
    family's scopes: the wire-format reader finds what
    ``jax.profiler.ProfileData`` finds, a scope no operation carries
    reads nothing, and so does an untraced run."""
    import jax

    with open(PROBE, "rb") as f:
        spans = qwen3next_readers.scope_intervals(f.read(),
                                                  "jit(step)/dot_general")
    plane = next(p for p in jax.profiler.ProfileData.from_file(PROBE).planes
                 if p.name == "/device:TPU:0")
    events = [e for line in plane.lines if line.name == trace_reduce.OPS_LINE
              for e in line.events
              if e.name.startswith("%convert_reduce_fusion")]
    assert len(spans) == len(events) == 3
    assert sum(e - s for s, e in spans) / 1e3 == pytest.approx(
        sum(e.duration_ns for e in events), rel=1e-3)
    with open(PROBE, "rb") as f:
        assert qwen3next_readers.scope_intervals(
            f.read(), "gated_delta_rule") == []
    # through the reader: the run's own file under benchmark_out/trace
    run = tmp_path / "benchmark_out" / "trace" / (CELL + "-7") / "plugins"
    run.mkdir(parents=True)
    shutil.copy(PROBE, run / "host.xplane.pb")
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    ctx = _ctx(_snaps(ROUND), rounds=3)
    got = qwen3next_readers.scope_ms_per_round(
        ctx, {"scope": "jit(step)/dot_general"})
    assert got == pytest.approx(
        sum(e.duration_ns for e in events) / 1e6 / 3, rel=1e-3)
    for name in ("qwen3next.gdn_scan_ms", "qwen3next.linear_layer_ms",
                 "qwen3next.gdn_scan_roofline"):
        spec = manifest.layer_metric_spec(name)
        assert manifest.resolve(spec["reader"])(ctx, spec) is None
    assert qwen3next_readers.scope_ms_per_round(
        _ctx(_snaps(ROUND), trace=False),
        {"scope": "jit(step)/dot_general"}) is None


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
    assert all(out["checks"].values()), out["checks"]
    # four of the rehearsal's sixteen experts are held: about a quarter
    assert 10.0 < out["metrics"]["qwen3next.local_row_share"]["value"] < 45.0
    assert "trainer.compute_ms" in out["metrics"]
    # the CPU has no device plane: the trace metrics are left out
    assert "qwen3next.gdn_scan_ms" not in out["metrics"]
    assert not [m for m in out["metrics"]
                if m.startswith(("moe.", "laguna."))]
