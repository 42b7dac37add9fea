"""BENCHMARK.json against the benchmark's rules, and every name in it
resolving to its files."""
import copy
import json

import pytest

from bench import harness, manifest

MAN = manifest.load()
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]


def test_manifest_is_sound():
    raw = (manifest.ROOT / "BENCHMARK.json").read_bytes()
    assert manifest.problems(MAN, len(raw)) == []


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    spec = manifest.cell(MAN, name)
    cfg, traffic = spec["config"], spec["traffic"]
    assert all(isinstance(cfg[k], int) and cfg[k] > 0 for k in cfg["axes"])
    assert traffic["loop"] in ("closed", "open")
    assert traffic["limits"] and all(v >= 0 for v in
                                     traffic["limits"].values())
    conf = {c["name"]: c for c in MAN["configs"]}[spec["cell"]["config"]]
    assert cfg["name"] == conf["name"]
    assert cfg["reduced"] == conf["reduced"]
    assert all(k in cfg for k in cfg["reduced"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_names_resolve_to_modules(name):
    spec = manifest.cell(MAN, name)
    assert manifest.cell_problems(spec) == []
    t = spec["traffic"]
    assert callable(manifest.module("entries", t["entry"]).build)
    assert callable(manifest.module("results", t["result"]).errors)
    assert hasattr(manifest.module("loops", t["loop"]), "Loop")
    for op, _ in t["graph"]:
        assert callable(manifest.module("stages", op).radius)


@pytest.mark.parametrize("where,name", [
    ("traffic", ("entry", "no_such_entry")),
    ("traffic", ("result", "no_such_result")),
    ("traffic", ("loop", "open-ended")),
    ("traffic", ("graph", [["no_such_stage", {}]])),
    ("traffic", ("graph", [["gaussian", {"sigma": 1.0, "padding": "wrap"}]])),
    ("config", ("maker", {"kind": "no_such_maker"})),
], ids=["entry", "result", "loop", "stage", "pad", "maker"])
def test_cell_problems_name_each_missing_file(where, name):
    spec = manifest.cell(MAN, CELLS[0])
    spec[where] = dict(spec[where], **{name[0]: name[1]})
    assert len(manifest.cell_problems(spec)) == 1


@pytest.mark.parametrize("name", METRICS)
def test_every_metric_has_a_reader(name):
    assert callable(harness._reader(name))


@pytest.mark.parametrize("name", CELLS)
def test_cell_reports_what_its_layers_move(name):
    e2e = {m["name"] for m in manifest.metrics_of(MAN, name, "end_to_end")}
    layers = manifest.metrics_of(MAN, name, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and layers
    assert all(m["moves"] in e2e for m in layers)


def test_layer_names_agree():
    by_layer = {}
    for m in MAN["per_layer"]:
        by_layer.setdefault(m["layer"], set()).add(m["name"])
    assert set(by_layer) <= {"planner and compile", "device", "kernels",
                             "sharding", "serving front end"}
    assert len(by_layer) >= 3


def _broken(edit):
    man = copy.deepcopy(MAN)
    edit(man)
    return manifest.problems(man)


@pytest.mark.parametrize("edit", [
    lambda m: m["workloads"][0].update(name="has space"),
    lambda m: m["end_to_end"][1].update(unit="tokens per second"),
    lambda m: m["per_layer"][0].update(moves="nope"),
    lambda m: m["per_layer"][0].update(why="extra key"),
    lambda m: m["end_to_end"][1].update(bound=0.5),
    lambda m: [w.update(chips=4) for w in m["workloads"]],
    lambda m: m["per_layer"][2]["workloads"].append("no-such-cell"),
    lambda m: m.update(run_seconds=60),
    lambda m: m["configs"].append(dict(m["configs"][0], name="unused")),
    lambda m: m["workloads"][0].update(traffic="no-such-traffic"),
], ids=["name", "unit", "moves", "extra-key", "bound", "four-chips",
        "moves-unreported", "run-seconds", "unused-config", "no-file"])
def test_problems_catch_each_breach(edit):
    assert _broken(edit)


def test_manifest_is_plain_json_under_64k():
    raw = (manifest.ROOT / "BENCHMARK.json").read_text()
    assert len(raw.encode()) <= 64 * 1024
    assert json.loads(raw) == MAN
