"""``BENCHMARK.json`` and the files a cell is made of, found by name.

A cell is an entry of ``workloads``. Its configuration is the ``file`` of the
``configs`` entry it names; its traffic mix is ``<pkg>/traffic/<traffic>.json``;
the driver, the model family and every metric reader are Python files found by
the names written in those data files:

    <pkg>/drivers/<traffic["driver"]>.py        run(ctx) -> Records
    <pkg>/families/<config["family"]>.py        model builder, plain reference, FLOPs/bytes
    <pkg>/end_to_end/<metric name>.py           read(records) -> number or None
    <pkg>/layer_metrics/<metric name>.py        read(records) -> number or None

``<pkg>`` is the first entry of ``paths``. Everything is loaded relative to a
``root`` directory, so a later PR (or a test, in a temporary directory) adds
a cell with new files and new entries only.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_manifest(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def load_module(root: str, pkg: str, kind: str, name: str):
    """The Python file ``<root>/<pkg>/<kind>/<name>.py`` as a module."""
    if not NAME.match(name):
        raise ManifestError(f"{kind} name {name!r} is not a name")
    path = os.path.join(root, pkg, kind, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(manifest: dict, group: str, cell: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports: those
    with no ``workloads`` key and those that list it."""
    return [m for m in manifest[group] if "workloads" not in m or cell in m["workloads"]]


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    driver: Any
    family: Any
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Any] = field(default_factory=dict)  # metric name -> module


def resolve_cell(manifest: dict, name: str, root: str) -> Cell:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise ManifestError(f"no workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in configs:
        raise ManifestError(f"workload {name!r} names config {w['config']!r}, not in configs")
    pkg = manifest["paths"][0]
    config = load_json(root, configs[w["config"]]["file"])
    traffic = load_json(root, os.path.join(pkg, "traffic", w["traffic"] + ".json"))
    cell = Cell(name=name, chips=int(w["chips"]), why=w["why"], config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                driver=load_module(root, pkg, "drivers", traffic["driver"]),
                family=load_module(root, pkg, "families", config["family"]),
                end_to_end=metrics_of(manifest, "end_to_end", name),
                per_layer=metrics_of(manifest, "per_layer", name))
    for m in cell.end_to_end:
        cell.readers[m["name"]] = load_module(root, pkg, "end_to_end", m["name"])
    for m in cell.per_layer:
        cell.readers[m["name"]] = load_module(root, pkg, "layer_metrics", m["name"])
    return cell


def check_manifest(manifest: dict, root: str) -> List[str]:
    """Every breach of the contract's limits on names, units and structure
    that can be seen without a run. Empty when the manifest is sound."""
    bad: List[str] = []

    def name_ok(what, value):
        if not isinstance(value, str) or not NAME.match(value):
            bad.append(f"{what}: {value!r} is not a name")

    def line_ok(what, value):
        if not isinstance(value, str) or not (1 <= len(value) <= 200) or "\n" in value or "\t" in value:
            bad.append(f"{what}: not one line of 1..200 characters")

    keys = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    if set(manifest) != keys:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(keys)}")
        return bad
    if not (1 <= int(manifest["run_seconds"]) <= 51):
        bad.append("run_seconds outside 1..51")
    if not (1 <= len(manifest["command"]) <= 32):
        bad.append("command length")
    for word in manifest["command"]:
        line_ok("command word", word)
        if word.startswith("/") or ".." in word.split("/"):
            bad.append(f"command word {word!r} leaves the repo")
    paths = manifest["paths"]
    if not (1 <= len(paths) <= 16):
        bad.append("paths count")
    for p in paths:
        if not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/") or ".." in p.split("/"):
            bad.append(f"path {p!r}")
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            name_ok(f"{group} name", entry.get("name"))
            key = (group if group in ("configs", "workloads") else "metric", entry.get("name"))
            if key in seen:
                bad.append(f"duplicate name {entry.get('name')!r}")
            seen.add(key)
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
        line_ok("config source", c.get("source"))
        line_ok("config why", c.get("why"))
        if not any(c.get("file", "").startswith(p.rstrip("/") + "/") for p in paths):
            bad.append(f"config file {c.get('file')!r} not under paths")
        if len(c.get("reduced", [])) > 16:
            bad.append("reduced too long")
        for k in c.get("reduced", []):
            name_ok("reduced key", k)
    files = [c.get("file") for c in manifest["configs"]]
    if len(set(files)) != len(files):
        bad.append("two configs share a file")
    pairs = set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys {sorted(w)}")
        name_ok("workload config", w.get("config"))
        name_ok("workload traffic", w.get("traffic"))
        line_ok("workload why", w.get("why"))
        if w.get("chips") not in (1, 4):
            bad.append(f"workload {w.get('name')}: chips {w.get('chips')}")
        if (w.get("config"), w.get("traffic")) in pairs:
            bad.append(f"pair {(w.get('config'), w.get('traffic'))} twice")
        pairs.add((w.get("config"), w.get("traffic")))
    used = {w.get("config") for w in manifest["workloads"]}
    for c in manifest["configs"]:
        if c.get("name") not in used:
            bad.append(f"config {c.get('name')!r} is used by no cell")
    cells = [w["name"] for w in manifest["workloads"]]
    if not (1 <= len(cells) <= 24):
        bad.append("cell count")
    four = sum(1 for w in manifest["workloads"] if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for m in manifest["end_to_end"]:
        if not set(m) <= {"name", "unit", "better", "bound", "source", "workloads"} or \
                not {"name", "unit", "better", "bound", "source"} <= set(m):
            bad.append(f"end_to_end {m.get('name')}: keys {sorted(m)}")
        if not (0 < float(m.get("bound", 0)) <= 0.1):
            bad.append(f"end_to_end {m.get('name')}: bound {m.get('bound')}")
        if m.get("source") not in ("host_clock", "device_trace"):
            bad.append(f"end_to_end {m.get('name')}: source {m.get('source')}")
    for m in manifest["per_layer"]:
        if not set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"} or \
                not {"name", "unit", "better", "source", "layer", "moves"} <= set(m):
            bad.append(f"per_layer {m.get('name')}: keys {sorted(m)}")
        line_ok("layer", m.get("layer"))
        if m.get("source") not in SOURCES:
            bad.append(f"per_layer {m.get('name')}: source {m.get('source')}")
        if m.get("moves") not in e2e:
            bad.append(f"per_layer {m.get('name')}: moves {m.get('moves')!r} is no end-to-end metric")
            continue
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            if "workloads" in moved and cell not in moved["workloads"]:
                bad.append(f"per_layer {m['name']} moves {m['moves']}, which cell {cell} does not report")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not isinstance(m.get("unit"), str) or not UNIT.match(m["unit"]):
            bad.append(f"metric {m.get('name')}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m.get('name')}: better {m.get('better')!r}")
        for cell in m.get("workloads", []):
            if cell not in cells:
                bad.append(f"metric {m.get('name')}: unknown workload {cell!r}")
    for cell in cells:
        if not [m for m in metrics_of(manifest, "end_to_end", cell) if m["name"] != "setup_s"]:
            bad.append(f"cell {cell} reports no end-to-end metric besides setup_s")
        if not metrics_of(manifest, "per_layer", cell):
            bad.append(f"cell {cell} reports no per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        bad.append("manifest over 64 KiB")
    return bad
