"""A CPU rehearsal of every cell at tiny width: it has to reach the last
line, mark it as no chip result, and exit 10. And the harness has to
take a new cell as data: the mesh cell, whose files are kept but which
is not in the manifest, is added to a COPY of it as entries, and runs.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest

ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           XLA_FLAGS="--xla_force_host_platform_device_count=4",
           PYTHONPATH=manifest.ROOT)


def rehearse(cell, trace, cwd=manifest.ROOT, seconds="2"):
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "2147483659", "--seconds", seconds, "--trace",
         str(trace), "--rehearse"],
        cwd=cwd, env=ENV, capture_output=True, text=True, timeout=600)
    assert p.returncode == 10, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] is True and out["device"]["platform"] == "cpu"
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    return out


@pytest.mark.parametrize("cell", [w["name"]
                                  for w in manifest.load()["workloads"]])
def test_every_cell_reaches_its_last_line(cell):
    man = manifest.load()
    out = rehearse(cell, 0)
    want = {m["name"] for m in manifest.metrics_of(man, "end_to_end", cell)}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    traced = rehearse(cell, 1)
    # no device trace on the CPU: only the host-side readers report
    assert "trainer.compute_ms" in traced["metrics"]
    assert "kv.messages_per_round" in traced["metrics"]


def test_without_a_tpu_there_is_no_result():
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "gpt2s-hips-bsc", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, env=ENV, capture_output=True, text=True,
        timeout=300)
    assert p.returncode not in (0, 10)
    assert not p.stdout.strip().splitlines()[-1:] or not \
        p.stdout.strip().splitlines()[-1].startswith("{")


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """The mesh cell, its configuration and its per-layer metric are kept
    as files and are not in the manifest yet (PERF.md section 7). Added
    to a COPY of the manifest as entries only, the cell runs: nothing
    that is there is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.load(open(os.path.join(manifest.ROOT, "BENCHMARK.json")))
    later = json.load(open(os.path.join(
        manifest.BENCH_DIR, "tests", "data", "mesh_cell_entries.json")))
    for group, entries in later.items():
        assert not {e["name"] for e in entries} & {
            e["name"] for e in man[group]}
        man[group].extend(entries)
    json.dump(man, open(root / "BENCHMARK.json", "w"))
    out = rehearse("gpt2m-mesh-bsc", 0, cwd=root)
    assert out["device"]["count"] == 4
    assert out["checks"]["replicas_equal"] is True
    traced = rehearse("gpt2m-mesh-bsc", 1, cwd=root)
    assert "trainer.wire_ms" in traced["metrics"]


def test_a_cell_with_three_parties_is_a_file_and_an_entry(tmp_path):
    """A throw-away cell written into a COPY of the checkout: three
    parties, so ``correct`` (b) sums three van speakers, and the groups
    that are handed through to the topology and to every node."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.load(open(os.path.join(manifest.ROOT, "BENCHMARK.json")))
    spec = manifest.load_cell("gpt2s-hips-bsc")["spec"]
    spec.update(name="throwaway-3p", num_parties=3,
                topology={"bigarray_bound": 500_000},
                extra_cfg={"heartbeat_interval_s": 0},
                trainer={"begin_key": 0})
    json.dump(spec, open(root / "benchmark" / "workloads" /
                         "throwaway-3p.json", "w"))
    man["workloads"].append({
        "name": "throwaway-3p", "config": "gpt2-small",
        "traffic": "hips-bsc-3p", "chips": 1, "why": "a test"})
    json.dump(man, open(root / "BENCHMARK.json", "w"))
    out = rehearse("throwaway-3p", 0, cwd=root)
    assert out["checks"]["aggregate"] and out["checks"]["select"]
    assert out["checks"]["apply"]
