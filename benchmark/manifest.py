"""``BENCHMARK.json`` and the data files it names.

The harness is driven by data: a cell, a configuration and a per-layer
metric are each a file of their own, found by the NAME the manifest
gives (see README.md for the three layouts):

    workloads[].name      -> benchmark/workloads/<name>.json
    configs[].file        -> the configuration as it is run
    per_layer[].name      -> benchmark/layer_metrics/<name>.json
    config "family"       -> benchmark/models/<family>.py,
                             benchmark/references/<family>.py

Names and units are held to the contract's character sets here, so a
bad one is refused before anything runs.
"""

from __future__ import annotations

import importlib
import json
import os
import re
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(
            f"{what} {name!r}: a name starts with a letter, a digit or _ "
            "and is made of at most 64 letters, digits, _, . and -")
    return name


def check_unit(unit, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ManifestError(
            f"{what}: unit {unit!r} must be 1 to 16 letters, digits, "
            "_, /, %, . and -")
    return unit


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def validate(man: dict) -> dict:
    """The checks a run depends on (the driver makes the rest)."""
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        if key not in man:
            raise ManifestError(f"BENCHMARK.json lacks {key!r}")
    seen: Dict[str, set] = {k: set() for k in ("config", "cell", "metric")}

    def once(kind: str, name: str) -> None:
        if name in seen[kind]:
            raise ManifestError(f"two {kind}s are named {name!r}")
        seen[kind].add(name)

    for c in man["configs"]:
        once("config", check_name(c.get("name"), "configuration"))
        for k in c.get("reduced", []):
            check_name(k, f"configuration {c['name']}: reduced key")
    for w in man["workloads"]:
        once("cell", check_name(w.get("name"), "cell"))
        check_name(w.get("traffic"), f"cell {w['name']}: traffic")
        if check_name(w.get("config"), "cell config") not in seen["config"]:
            raise ManifestError(f"cell {w['name']}: no configuration "
                                f"{w['config']!r}")
        if w.get("chips") not in (1, 4):
            raise ManifestError(f"cell {w['name']}: chips is 1 or 4")
    e2e = set()
    for m in man["end_to_end"] + man["per_layer"]:
        once("metric", check_name(m.get("name"), "metric"))
        check_unit(m.get("unit"), f"metric {m['name']}")
        if m.get("better") not in ("lower", "higher"):
            raise ManifestError(f"metric {m['name']}: better is lower or "
                                "higher")
        if m.get("source") not in SOURCES:
            raise ManifestError(f"metric {m['name']}: source is one of "
                                f"{SOURCES}")
        for cell in m.get("workloads", []):
            if cell not in seen["cell"]:
                raise ManifestError(f"metric {m['name']}: no cell {cell!r}")
        if "bound" in m:
            e2e.add(m["name"])
    for m in man["per_layer"]:
        if m.get("moves") not in e2e:
            raise ManifestError(f"metric {m['name']}: moves "
                                f"{m.get('moves')!r}, not an end-to-end "
                                "metric")
    if "setup_s" not in e2e:
        raise ManifestError("end_to_end lacks setup_s")
    return man


def load(root: str = ROOT) -> dict:
    return validate(_read_json(os.path.join(root, "BENCHMARK.json")))


def load_config_file(name: str, man: dict = None, root: str = ROOT) -> dict:
    """The configuration as it is run, by its name in the manifest."""
    man = man or load(root)
    for c in man["configs"]:
        if c["name"] == name:
            return _read_json(os.path.join(root, c["file"]))
    raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")


def load_cell(name: str, man: dict = None, root: str = ROOT) -> dict:
    """A cell: its manifest entry, its own file of parameters, and its
    configuration. ``KeyError``-free: a missing name is an error."""
    man = man or load(root)
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        raise ManifestError(
            f"no cell {name!r}; BENCHMARK.json has "
            f"{[w['name'] for w in man['workloads']]}")
    spec = _read_json(os.path.join(root, "benchmark", "workloads",
                                   name + ".json"))
    return {"entry": entry, "spec": spec,
            "config": load_config_file(entry["config"], man, root)}


def metrics_of(man: dict, group: str, cell: str) -> List[dict]:
    """The ``group`` metrics this cell reports: those that list it, and
    those that list no cells at all."""
    return [m for m in man[group]
            if "workloads" not in m or cell in m["workloads"]]


def layer_metric_spec(name: str, root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "benchmark", "layer_metrics",
                                   name + ".json"))


def family_module(kind: str, family: str):
    """``benchmark/<kind>/<family>.py`` (kind: models | references)."""
    return importlib.import_module(
        f"benchmark.{kind}.{check_name(family, 'model family')}")


def resolve(dotted: str):
    """``module:function`` under the benchmark's own package."""
    mod, _, fn = dotted.partition(":")
    if not fn:
        mod, fn = "readers", mod
    return getattr(importlib.import_module(f"benchmark.{mod}"), fn)


def peaks_for(device_kind: str) -> dict:
    table = _read_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise ManifestError(
            f"no peaks for device_kind {device_kind!r} in peaks.json "
            f"(has {sorted(table)}); an unknown device is an error")
    return table[device_kind]
