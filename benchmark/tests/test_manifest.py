import copy
import os

import pytest

from benchmark import manifest


def test_the_manifest_is_valid_and_its_files_exist():
    man = manifest.load()
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    for c in man["configs"]:
        cfg = manifest.load_config_file(c["name"], man)
        assert cfg["reduced"] == c["reduced"]
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
        for kind in ("models", "references"):
            manifest.family_module(kind, cfg["family"])
    for w in man["workloads"]:
        cell = manifest.load_cell(w["name"], man)
        assert cell["spec"]["config"] == w["config"]
        assert cell["spec"]["chips"] == w["chips"]
        assert 1 <= len(w["why"]) <= 200
        assert manifest.metrics_of(man, "per_layer", w["name"])
    for m in man["per_layer"]:
        spec = manifest.layer_metric_spec(m["name"])
        assert callable(manifest.resolve(spec["reader"]))
        for k in ("unit", "layer", "source", "moves"):
            assert spec[k] == m[k]
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= max(
        1, len(man["workloads"]) // 4)


@pytest.mark.parametrize("bad", ["tokens per s", "a,b", "a/b", "", "-x",
                                 "x" * 65, "mµ"])
def test_a_bad_name_is_refused(bad):
    man = copy.deepcopy(manifest.load())
    man["per_layer"][0]["name"] = bad
    with pytest.raises(manifest.ManifestError):
        manifest.validate(man)
    man = copy.deepcopy(manifest.load())
    man["workloads"][0]["name"] = bad
    with pytest.raises(manifest.ManifestError):
        manifest.validate(man)


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "",
                                 "x" * 17, "a,b"])
def test_a_bad_unit_is_refused(bad):
    man = copy.deepcopy(manifest.load())
    man["end_to_end"][0]["unit"] = bad
    with pytest.raises(manifest.ManifestError):
        manifest.validate(man)


def test_good_units_pass():
    for unit in ("tokens/s/chip", "ms", "%", "MB", "count", "us"):
        manifest.check_unit(unit, "x")


def test_unknown_names_are_errors():
    man = manifest.load()
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell("no-such-cell", man)
    with pytest.raises(manifest.ManifestError):
        manifest.load_config_file("no-such-config", man)
    dup = copy.deepcopy(man)
    dup["workloads"].append(dict(dup["workloads"][0]))
    with pytest.raises(manifest.ManifestError):
        manifest.validate(dup)
    moved = copy.deepcopy(man)
    moved["per_layer"][0]["moves"] = "nothing"
    with pytest.raises(manifest.ManifestError):
        manifest.validate(moved)


def test_files_are_named_from_name_characters():
    for base, _dirs, files in os.walk(manifest.BENCH_DIR):
        if "__pycache__" in base:
            continue
        for f in files:
            assert manifest.NAME_RE.match(f), os.path.join(base, f)
