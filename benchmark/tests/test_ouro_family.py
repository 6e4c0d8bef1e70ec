"""The ``ouro`` family as benchmark data: the configuration against the
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

from benchmark import manifest, ouro_readers, qwen3next_readers, readers

CELL, CONFIG = "ouro-vp8-hips-bsc-4k", "ouro-2.6b-vp8"
CUT = {"num_hidden_layers", "layer_types", "vocab_size"}
METRICS = ["ouro.loop_ms", "ouro.exit_ms", "ouro.attn_core_ms",
           "ouro.attn_core_roofline", "ouro.last_exit_share"]
PROBE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "v5e_probe.xplane.pb")
# T(T+1)/2 at 4,096
LIVE = 8_390_656


def _cfg():
    return manifest.load_config_file(CONFIG)


def test_configuration_keeps_every_published_width():
    cfg = _cfg()
    assert set(cfg["reduced"]) == CUT
    for key, value in cfg["published"].items():
        assert (cfg[key] == value) != (key in CUT), key
    for key, value in {
            "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 16,
            "num_key_value_heads": 16, "intermediate_size": 5632,
            "hidden_act": "silu", "rope_theta": 1000000,
            "rope_scaling": None, "rms_norm_eps": 1e-6,
            "tie_word_embeddings": False, "model_type": "ouro",
            "total_ut_steps": 4, "early_exit_threshold": 1}.items():
        assert cfg[key] == value, key
    depth = cfg["num_hidden_layers"]
    assert depth >= 4 and cfg["published"]["num_hidden_layers"] == 48
    assert cfg["layer_types"] == ["full_attention"] * depth
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert set(cfg["assumed"]) >= {
        "sandwich_norms", "loop", "exit_gate", "exit_distribution",
        "objective", "entropy_beta", "gate_stage_two",
        "early_exit_threshold", "biases", "rope_layout",
        "training_length", "initializer_range", "microbatch_sequences"}
    assert set(cfg) >= {"departures", "deployment", "sizes", "rehearsal",
                        "control_dtype", "limits", "limits_read", "step_0"}
    assert set(cfg["deployment"]) >= {"stands_for", "this_chip",
                                      "not_imitated"}
    # both readings that set the limit are in the file, with room
    read = cfg["limits_read"]
    assert max(read["program_grad_rel_l2"]) < cfg["limits"]["grad_rel_l2"] \
        < min(read["control_float8_e4m3fn"])


def test_manifest_entries():
    man = manifest.load()
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    cfg = _cfg()
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = manifest.load_cell(CELL, man)
    assert cell["entry"] == {
        "name": CELL, "config": CONFIG, "traffic": "hips-bsc-4k-b2",
        "chips": 1, "why": cell["spec"]["why"]}
    assert len(cell["entry"]["why"]) <= 200 and len(entry["why"]) <= 200
    # Kanana's cell file to the letter but for name, config, why and the
    # batch: two sequences of 4,096 a worker
    other = manifest.load_cell("kanana2-ep16-hips-bsc-8k", man)["spec"]
    same = set(other) - {"name", "why", "config", "limits_read",
                         "batch_per_worker", "seq_len"}
    assert {k: cell["spec"][k] for k in same} == {k: other[k] for k in same}
    assert (cell["spec"]["batch_per_worker"], cell["spec"]["seq_len"]) \
        == (2, 4096)
    mine = [m["name"] for m in man["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == METRICS
    for name in mine:
        spec = manifest.layer_metric_spec(name)
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert entry["moves"] == "tokens_per_s_per_chip"
        for key in ("unit", "layer", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert callable(manifest.resolve(spec["reader"]))
    assert CELL not in [c for m in man["per_layer"]
                        if not m["name"].startswith("ouro.")
                        for c in m.get("workloads", [])]
    # the metric that reads nothing since PR 61 lists the cells it had
    scatter = next(m for m in man["per_layer"]
                   if m["name"] == "step.apply_scatter_ms")
    assert scatter["workloads"] == [w["name"] for w in man["workloads"][:8]]


def test_ouro_share_hand_count():
    cfg = _cfg()
    depth = cfg["num_hidden_layers"]
    ref = manifest.family_module("references", cfg["family"])
    assert ref.live_score_entries(cfg, 4096) == 16 * depth * 4 * LIVE
    # a token through a layer application: q, k, v, o 4 * 2 * 2048 * 2048
    # = 33,554,432; gate, up, down 3 * 2 * 2048 * 5632 = 69,206,016; 16
    # heads * 2,048.5 live keys * 4 * 128 = 16,781,312. An exit: the head
    # 2 * 2048 * 6144 = 25,165,824 and the gate 4,096
    a_layer = 33_554_432 + 69_206_016 + 16_781_312
    forward = 4 * (depth * a_layer + 25_165_824 + 4_096)
    assert ref.forward_flops_per_token(cfg, 4096) == forward
    assert ref.train_flops_per_token(cfg, 4096) == 3 * forward
    assert ref.num_params(cfg) == cfg["sizes"]["parameters"] \
        == depth * 51_388_416 + 2 * 12_582_912 + 2_048 + 2_049
    assert len(ref.param_shapes(cfg)) == cfg["sizes"]["keys"] \
        == 11 * depth + 5
    assert cfg["sizes"]["a_layer"]["total"] == sum(
        v for k, v in cfg["sizes"]["a_layer"].items() if k != "total")
    # the kernels compute 20 live tiles of 512 x 1,024 a head
    from geomx_tpu.models.transformer import (kernel_score_entries,
                                              score_entries)
    assert score_entries(4096) == (LIVE, 16_777_216)
    assert kernel_score_entries(4096, 128) == 10_485_760


def _ctx(snaps, rounds=2, trace=True, cfg=None):
    return readers.Context(
        cell=CELL, chips=1, peaks=manifest.peaks_for("TPU v5 lite"),
        rounds=4, snaps=snaps,
        trace={"rounds": rounds, "op_seconds_first_chip": {}}
        if trace else None,
        tokens_traced=rounds * 2 * 2 * 4096, reference=None,
        cfg=cfg or _cfg(), seq_len=4096)


def _snaps(per_round, n=5):
    return [{"counters": {name: 7.0 + i * value
                          for name, value in per_round.items()}}
            for i in range(n)]


def _round(cfg):
    """A round's counters: 2 workers x 2 sequences."""
    applications = cfg["num_hidden_layers"] * 4
    return {"ouro.positions": 4 * 4096,
            "ouro.layer_applications": 4 * applications,
            "attn.score_entries_live": 4 * applications * 16 * LIVE,
            "attn.score_entries_computed": 4 * applications * 16 * 10_485_760,
            "ouro.exit_mass_t1": 4 * 4096 * 0.5,
            "ouro.exit_mass_t2": 4 * 4096 * 0.25,
            "ouro.exit_mass_t3": 4 * 4096 * 0.125,
            "ouro.exit_mass_t4": 4 * 4096 * 0.125}


def test_the_named_readers_on_a_synthetic_run(monkeypatch):
    cfg = _cfg()
    applications = cfg["num_hidden_layers"] * 4
    ctx = _ctx(_snaps(_round(cfg)))
    # the core, two traced rounds of four sequences: live entries of
    # 6 * 256 operations against (position, head, application) triples of
    # 16 * 128 bytes: the operations bound it
    live = 2 * 4 * applications * 16 * LIVE
    triples = 2 * 4 * 4096 * 16 * applications
    need = ouro_readers.causal_core_need(cfg, live, triples)
    assert need == {"flops": live * 1536.0, "bytes": triples * 2048.0}
    assert need["flops"] / 197e12 > need["bytes"] / 819e9
    spec = manifest.layer_metric_spec("ouro.attn_core_roofline")
    assert spec["scope"] == "causal_core"
    monkeypatch.setattr(qwen3next_readers, "scope_ms_per_round",
                        lambda ctx, spec: 200.0)
    got = ouro_readers.attn_core_roofline(ctx, spec)
    assert got == pytest.approx(100 * (need["flops"] / 197e12) / 0.4)
    assert 0.0 < got < 100.0
    spec = manifest.layer_metric_spec("ouro.last_exit_share")
    assert ouro_readers.last_exit_share(ctx, spec) == pytest.approx(12.5)
    for name, scope in (("ouro.loop_ms", "ouro_loop"),
                        ("ouro.exit_ms", "ouro_exit"),
                        ("ouro.attn_core_ms", "causal_core")):
        spec = manifest.layer_metric_spec(name)
        assert spec["scope"] == scope
        assert spec["reader"] == "qwen3next_readers:scope_ms_per_round"


def test_a_program_without_scopes_or_counters_reports_nothing(
        tmp_path, monkeypatch):
    """What the new entries read from the parent commit, which has no
    ``ouro`` family, and from another family's trace: nothing, without
    raising. The recorded v5e probe carries none of the scopes."""
    bare = [{"counters": {"van.messages_sent": 8.0 * i}} for i in range(5)]
    run = tmp_path / "benchmark_out" / "trace" / (CELL + "-7") / "plugins"
    run.mkdir(parents=True)
    shutil.copy(PROBE, run / "host.xplane.pb")
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    assert qwen3next_readers.scope_ms_per_round(
        _ctx(bare), {"scope": "jit(step)/dot_general"}) > 0
    cfg = _cfg()
    for ctx in (_ctx(bare), _ctx(_snaps(_round(cfg))),
                _ctx(bare, trace=False)):
        for name in METRICS:
            if name == "ouro.last_exit_share" and \
                    "ouro.positions" in ctx.snaps[-1]["counters"]:
                continue
            spec = manifest.layer_metric_spec(name)
            assert manifest.resolve(spec["reader"])(ctx, spec) is None, name
    # another family's configuration is not read by this family's keys
    other = _ctx(_snaps(_round(cfg)),
                 cfg=manifest.load_config_file("mellum2-12b-ep8"))
    monkeypatch.setattr(qwen3next_readers, "scope_ms_per_round",
                        lambda ctx, spec: 200.0)
    for name in ("ouro.attn_core_roofline", "ouro.last_exit_share"):
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
    # seeded weights: every gate near 0.5, the last exit about an eighth
    assert 8.0 < out["metrics"]["ouro.last_exit_share"]["value"] < 18.0
    assert "server.bsc_select_ms" in out["metrics"]
    assert "step.apply_scatter_ms" not in out["metrics"]
    assert not [m for m in out["metrics"]
                if m.startswith(("laguna.", "mellum.", "kanana.", "sdar."))]
