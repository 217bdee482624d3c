"""Discovery and validation: from a cell's name in ``BENCHMARK.json`` to
its configuration, traffic, limits, modules and metric readers, each a
file of its own under ``bench/``.  Nothing here names a cell, a
configuration or a metric: a cell added as new files and entries is
found without an edit to this file."""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

#: The checkout: ``bench/`` sits at its root.
ROOT = Path(__file__).resolve().parents[1]

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
_SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
_TOP = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by path, under a name of its own."""
    path = Path(path)
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:])
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _line(text) -> bool:
    return (isinstance(text, str) and 0 < len(text) <= 200
            and "\n" not in text and "\t" not in text)


def validate(bench: dict, root: Path = ROOT) -> List[str]:
    """Everything wrong with ``bench`` (empty when it is sound): the
    contract's shape, and every file a cell needs."""
    root = Path(root)
    bad: List[str] = []
    if set(bench) != _TOP:
        bad.append(f"top-level keys {sorted(bench)}")
    for section, keys in _KEYS.items():
        names = set()
        for e in bench.get(section, []):
            extra = set(e) - keys - ({"workloads"} if section in
                                     ("end_to_end", "per_layer") else set())
            if extra or not keys <= set(e):
                bad.append(f"{section} entry {e.get('name')}: keys "
                           f"{sorted(e)}")
                continue
            if not _NAME.match(e["name"]) or e["name"] in names:
                bad.append(f"{section}: bad or repeated name {e['name']!r}")
            names.add(e["name"])
            if "unit" in e and not _UNIT.match(e["unit"]):
                bad.append(f"{e['name']}: unit {e['unit']!r}")
            if "better" in e and e["better"] not in ("lower", "higher"):
                bad.append(f"{e['name']}: better {e['better']!r}")
            if "source" in e and section in ("end_to_end", "per_layer") \
                    and e["source"] not in _SOURCES:
                bad.append(f"{e['name']}: source {e['source']!r}")
            for key in ("why", "layer"):
                if key in e and not _line(e[key]):
                    bad.append(f"{e['name']}: {key} not one line of "
                               f"1-200 characters")
    e2e = {m["name"] for m in bench.get("end_to_end", [])}
    for m in bench.get("per_layer", []):
        if m.get("moves") not in e2e:
            bad.append(f"{m['name']}: moves {m.get('moves')!r}")
        if not (root / "bench" / "metrics" / f"{m['name']}.py").exists():
            bad.append(f"{m['name']}: no reader bench/metrics/"
                       f"{m['name']}.py")
    cells = {w["name"] for w in bench.get("workloads", [])}
    for m in bench.get("end_to_end", []) + bench.get("per_layer", []):
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']}: unknown workload {w!r}")
    pairs = set()
    for w in bench.get("workloads", []):
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"{w['name']}: repeated config and traffic")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            bad.append(f"{w['name']}: chips {w['chips']}")
        try:
            load_cell(w["name"], root, bench, import_modules=False)
        except (KeyError, OSError, ValueError) as exc:
            bad.append(f"{w['name']}: {exc}")
    return bad


class Cell:
    """One workload with everything it reads."""

    def __init__(self, entry: dict, config: dict, traffic: dict,
                 limits: dict, root: Path):
        self.name = entry["name"]
        self.chips = int(entry["chips"])
        self.config = config
        self.traffic = traffic
        self.limits = limits
        self.root = root
        self.model: Optional[ModuleType] = None
        self.datagen: Optional[ModuleType] = None
        self.end_to_end: List[dict] = []
        self.per_layer: List[dict] = []
        self.readers: Dict[str, ModuleType] = {}

    @property
    def num_devices(self) -> int:
        return int(self.traffic.get("num_devices",
                                    self.config["num_devices"]))


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT, bench: Optional[dict] = None,
              import_modules: bool = True) -> Cell:
    """The cell ``name``: its configuration file, ``bench/traffic/
    <traffic>.json``, ``bench/limits/<cell>.json``, the model and data
    modules its configuration names, and the readers of the per-layer
    metrics it reports."""
    root = Path(root)
    bench = load_benchmark(root) if bench is None else bench
    entry = next((w for w in bench["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise KeyError(f"no config {entry['config']!r} in BENCHMARK.json")
    config = _read_json(root / cfg_entry["file"])
    traffic = _read_json(root / "bench" / "traffic"
                         / f"{entry['traffic']}.json")
    limits = _read_json(root / "bench" / "limits" / f"{name}.json")
    cell = Cell(entry, config, traffic, limits, root)
    model = root / "bench" / "models" / f"{config['model']}.py"
    datagen = root / "bench" / "datagen" / f"{config['data']['generator']}.py"
    for path in (model, datagen):
        if not path.exists():
            raise OSError(f"missing {path.relative_to(root)}")

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    cell.end_to_end = [m for m in bench["end_to_end"] if applies(m)]
    cell.per_layer = [m for m in bench["per_layer"] if applies(m)]
    if import_modules:
        cell.model = load_module(model)
        cell.datagen = load_module(datagen)
        cell.readers = {m["name"]: load_module(
            root / "bench" / "metrics" / f"{m['name']}.py")
            for m in cell.per_layer}
    return cell
