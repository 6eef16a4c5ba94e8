"""BENCHMARK.json against the contract's limits, and every cell resolved from
names alone — also a cell made of new files only, in a temporary root."""
import json
import os

import pytest

import tiny_root
from benchmark.harness import manifest

REPO = tiny_root.REPO


@pytest.fixture(scope="module")
def real():
    return manifest.load_manifest(REPO)


def test_manifest_meets_the_contract(real):
    assert manifest.check_manifest(real, REPO) == []
    assert real["command"] == ["python3", "-m", "benchmark.run"]


@pytest.mark.parametrize("broken, complaint", [
    (lambda m: m["workloads"][0].update(name="has space"), "not a name"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per s"), "unit"),
    (lambda m: m["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "is no end-to-end metric"),
    (lambda m: m["per_layer"][0].update(why="x"), "keys"),
    (lambda m: [w.update(chips=4) for w in m["workloads"][:2]], "four-chip cells"),
    (lambda m: m["per_layer"][0].pop("workloads"), "does not report"),
    (lambda m: m["workloads"][0].update(why="a" * 201), "one line"),
])
def test_a_broken_manifest_is_named(real, broken, complaint):
    m = json.loads(json.dumps(real))
    broken(m)
    assert any(complaint in b for b in manifest.check_manifest(m, REPO)), manifest.check_manifest(m, REPO)


def test_every_cell_resolves_by_name(real):
    for w in real["workloads"]:
        cell = manifest.resolve_cell(real, w["name"], REPO)
        assert callable(cell.driver.run)
        assert callable(cell.family.build_model) and callable(cell.family.reference_logits)
        names = [m["name"] for m in cell.end_to_end + cell.per_layer]
        assert "setup_s" in names and len(cell.end_to_end) >= 2 and cell.per_layer
        for n in names:
            assert callable(cell.readers[n].read), n


def test_every_layer_metric_moves_a_metric_its_cells_report(real):
    cells = [w["name"] for w in real["workloads"]]
    for m in real["per_layer"]:
        for c in m.get("workloads", cells):
            reported = [e["name"] for e in manifest.metrics_of(real, "end_to_end", c)]
            assert m["moves"] in reported, (m["name"], c)
    assert sum(w["chips"] == 4 for w in real["workloads"]) <= max(1, len(cells) // 4)


def test_files_are_where_the_manifest_says(real):
    pkg = real["paths"][0]
    for c in real["configs"]:
        cfg = manifest.load_json(REPO, c["file"])
        assert cfg["reduced"] == c["reduced"] == []
        assert cfg["source"] == c["source"]
    for w in real["workloads"]:
        assert os.path.isfile(os.path.join(REPO, pkg, "traffic", w["traffic"] + ".json"))
    # nothing in harness/ or run.py names a configuration, a mix or a metric
    names = {x["name"] for g in ("configs", "workloads", "end_to_end", "per_layer") for x in real[g]}
    names |= {w["traffic"] for w in real["workloads"]}
    names -= {"setup_s"}  # a field of the records, which every cell has
    for dirpath, _, files in os.walk(os.path.join(REPO, pkg, "harness")):
        for f in [os.path.join(dirpath, f) for f in files if f.endswith(".py")] + [os.path.join(REPO, pkg, "run.py")]:
            text = open(f).read()
            assert not [n for n in names if n in text], f


def test_a_cell_of_new_files_only_is_loaded(tmp_path):
    """What a later PR brings: a configuration file, a traffic file, a driver,
    a metric reader and entries naming them. No file that is there changes."""
    root = tiny_root.make(tmp_path)
    pkg = os.path.join(root, "benchmark")
    for sub in ("drivers", "layer_metrics"):   # the new PR's own directories hold the old files and its new ones
        link = os.path.join(pkg, sub)
        target = os.readlink(link)
        os.unlink(link)
        os.makedirs(link)
        for f in os.listdir(target):
            os.symlink(os.path.join(target, f), os.path.join(link, f))
    open(os.path.join(pkg, "drivers", "replay.py"), "w").write("def run(records, devices, **kw):\n    records.notes['ran'] = 'replay'\n")
    open(os.path.join(pkg, "layer_metrics", "dispatch_ms.serve.py"), "w").write("def read(records):\n    return 1.5\n")
    json.dump({"driver": "replay", "file": "trace.csv"}, open(os.path.join(pkg, "traffic", "replayed.json"), "w"))
    m = manifest.load_manifest(root)
    m["workloads"].append({"name": "tiny.replayed", "config": "tiny", "traffic": "replayed", "chips": 1, "why": "new"})
    m["per_layer"].append({"name": "dispatch_ms.serve", "unit": "ms", "better": "lower", "source": "host_clock",
                           "layer": "engine", "moves": "setup_s", "workloads": ["tiny.replayed"]})
    for e in m["end_to_end"]:
        if e["name"] == "itl_p95_ms":
            e["workloads"].append("tiny.replayed")
    assert manifest.check_manifest(m, root) == []
    cell = manifest.resolve_cell(m, "tiny.replayed", root)
    assert cell.traffic["file"] == "trace.csv" and cell.readers["dispatch_ms.serve"].read(None) == 1.5

    class R:
        notes = {}
    cell.driver.run(R, [])
    assert R.notes["ran"] == "replay"
