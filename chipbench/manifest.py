"""``BENCHMARK.json`` and the files it names, found by name.

A cell names its configuration and its traffic; the harness looks them up
as ``chipbench/configs/<config>.json`` and ``chipbench/mixes/<traffic>.json``
and a per-layer metric as ``chipbench/metrics/<name>.py`` (or, for a name
split by cell such as ``idle_share.chat``, the reader of the part before
the first dot). A manifest elsewhere (a test's) has its files looked up
beside it first, then in this checkout, so a test can add a
configuration, a mix or a cell from data alone.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Sequence

PKG_ROOT = Path(__file__).resolve().parent.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]


class Bench:
    """The manifest plus the roots its named files are searched under."""

    def __init__(self, doc: Dict, roots: Sequence[Path]):
        self.doc = doc
        self.roots = [Path(r) for r in roots]

    @classmethod
    def load(cls, root: Path = PKG_ROOT) -> "Bench":
        """The manifest at ``root``; files it names are looked up there
        first, then in this checkout."""
        root = Path(root)
        doc = json.loads((root / "BENCHMARK.json").read_text())
        return cls(doc, [root] if root == PKG_ROOT else [root, PKG_ROOT])

    def _find(self, sub: str, name: str, ext: str) -> Path:
        if not NAME_RE.match(name):
            raise ValueError(f"bad name {name!r}")
        for r in self.roots:
            p = r / "chipbench" / sub / f"{name}{ext}"
            if p.is_file():
                return p
        raise FileNotFoundError(f"no chipbench/{sub}/{name}{ext}")

    def config_path(self, name: str) -> Path:
        return self._find("configs", name, ".json")

    def mix_path(self, name: str) -> Path:
        return self._find("mixes", name, ".json")

    def reader(self, metric: str) -> Callable:
        """The per-layer reader ``read(record) -> float | None``."""
        for cand in (metric, metric.split(".", 1)[0]):
            try:
                path = self._find("metrics", cand, ".py")
            except FileNotFoundError:
                continue
            spec = importlib.util.spec_from_file_location(
                f"chipbench_metric_{cand.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
        raise FileNotFoundError(f"no reader for metric {metric!r}")

    def cell(self, name: str) -> Cell:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return Cell(name, w["config"], w["traffic"], int(w["chips"]),
                            metrics_of(self.doc["end_to_end"], name),
                            metrics_of(self.doc["per_layer"], name))
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(metrics: List[Dict], cell: str) -> List[Dict]:
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def problems(doc: Dict) -> List[str]:
    """What in a manifest breaks the benchmark's rules (empty when none):
    keys, names, units, sources, and that every cell reporting a per-layer
    metric also reports the end-to-end metric it moves."""
    out: List[str] = []
    if set(doc) != TOP_KEYS:
        out.append(f"top-level keys {sorted(doc)}")
    names = {}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in doc.get(kind, []):
            n = e.get("name", "")
            if not NAME_RE.match(n):
                out.append(f"{kind}: bad name {n!r}")
            names.setdefault(kind, []).append(n)
        seen = names.get(kind, [])
        if len(seen) != len(set(seen)):
            out.append(f"{kind}: duplicate names")
    metric_names = names.get("end_to_end", []) + names.get("per_layer", [])
    if len(metric_names) != len(set(metric_names)):
        out.append("a metric name is used twice")
    cells = {w["name"]: w for w in doc.get("workloads", [])}
    configs = set(names.get("configs", []))
    for w in cells.values():
        if w.get("config") not in configs:
            out.append(f"{w['name']}: unknown config")
        if w.get("chips") not in (1, 4):
            out.append(f"{w['name']}: chips must be 1 or 4")
        if not NAME_RE.match(w.get("traffic", "")):
            out.append(f"{w['name']}: bad traffic name")
    used = {w["config"] for w in cells.values()}
    if configs - used:
        out.append(f"configs without a cell: {sorted(configs - used)}")
    e2e = {m["name"]: m for m in doc.get("end_to_end", [])}
    if "setup_s" not in e2e:
        out.append("no setup_s")
    for m in doc.get("end_to_end", []) + doc.get("per_layer", []):
        if not UNIT_RE.match(m.get("unit", "")):
            out.append(f"{m.get('name')}: bad unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            out.append(f"{m.get('name')}: better must be lower or higher")
        for c in m.get("workloads", []):
            if c not in cells:
                out.append(f"{m['name']}: unknown cell {c}")
    for m in doc.get("end_to_end", []):
        if m.get("source") not in SOURCES_E2E:
            out.append(f"{m['name']}: end-to-end source {m.get('source')}")
        if not 0.01 <= m.get("bound", 0) <= 0.25:
            out.append(f"{m['name']}: bound out of [0.01, 0.25]")
    for m in doc.get("per_layer", []):
        if m.get("source") not in SOURCES:
            out.append(f"{m['name']}: source {m.get('source')}")
        if m.get("moves") not in e2e:
            out.append(f"{m['name']}: moves unknown {m.get('moves')}")
            continue
        for c in m.get("workloads", list(cells)):
            if c not in [x for x in cells
                         if x in e2e[m["moves"]].get("workloads", cells)]:
                out.append(f"{m['name']}: cell {c} does not report "
                           f"{m['moves']}")
    for c in cells:
        rep = [m["name"] for m in metrics_of(doc.get("end_to_end", []), c)]
        if "setup_s" not in rep or len(rep) < 2:
            out.append(f"{c}: needs setup_s and another end-to-end metric")
        if not metrics_of(doc.get("per_layer", []), c):
            out.append(f"{c}: no per-layer metric")
    return out
