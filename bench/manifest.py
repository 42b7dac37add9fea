"""``BENCHMARK.json``: load it, check it, and resolve its names to files.

A cell (an entry of ``workloads``) names a configuration, found as the
``file`` its entry in ``configs`` gives, and a traffic mix, found as
``bench/traffic/<traffic>.json``.  Every metric is read by
``bench/metrics/<name>.py``.  The names inside those files resolve the
same way, each to a module of its own (:func:`module`):

- the traffic's ``loop`` to ``bench/loops/<loop>.py``, its ``entry`` to
  ``bench/entries/<entry>.py`` and its ``result`` to
  ``bench/results/<result>.py``;
- each stage of its ``graph`` to ``bench/stages/<op>.py``, and the
  padding each stage reads its input with to ``bench/pads/<pad>.py``;
- the configuration's ``maker.kind`` to ``bench/makers/<kind>.py``.

So a cell is added by adding files and entries.  :func:`problems` lists
every way the file breaks the benchmark's rules, and
:func:`cell_problems` every name of a cell that resolves to no file; an
empty list means it is sound.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

__all__ = ["ROOT", "BENCH", "load", "problems", "cell", "cell_problems",
           "metrics_of", "reader_path", "traffic_path", "module",
           "module_path", "pad_of"]

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
E2E_SOURCES = {"host_clock", "device_trace"}
SOURCES = E2E_SOURCES | {"program_span", "program_counter"}


def load(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def traffic_path(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def reader_path(metric: str) -> Path:
    return BENCH / "metrics" / f"{metric}.py"


def module_path(kind: str, name: str) -> Path:
    return BENCH / kind / f"{name}.py"


_MODULES: dict = {}


def module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded once per process."""
    key = (kind, name)
    if key not in _MODULES:
        path = module_path(kind, name)
        if not NAME.match(name) or not path.is_file():
            raise KeyError(f"no {kind} named {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


def pad_of(kw: dict, pad_value: str) -> str:
    """The padding a stage reads its input with: its own ``padding``
    where that is not 'same', else the run's ``pad_value``."""
    p = kw.get("padding", "same")
    return pad_value if p == "same" else p


def metrics_of(man: dict, cell_name: str, kind: str) -> list:
    """The metrics of ``kind`` (``end_to_end`` / ``per_layer``) that the
    cell reports: those with no ``workloads`` key, and those listing it."""
    return [m for m in man[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def cell(man: dict, name: str) -> dict:
    """The cell with its configuration and traffic files read:
    ``{"cell", "config", "traffic", "chips"}``."""
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    return {"cell": w, "chips": int(w["chips"]),
            "config": json.loads((ROOT / conf["file"]).read_text()),
            "traffic": json.loads(traffic_path(w["traffic"]).read_text())}


def cell_problems(spec: dict) -> list:
    """Every name of a cell's configuration and traffic that resolves to
    no module."""
    t, cfg = spec["traffic"], spec["config"]
    want = [("loops", t.get("loop")), ("entries", t.get("entry")),
            ("results", t.get("result")),
            ("makers", cfg.get("maker", {}).get("kind"))]
    for op, kw in t.get("graph", []):
        want += [("stages", op), ("pads", pad_of(kw, t.get("pad_value")))]
    return [f"{kind}: no file for {name!r}" for kind, name in want
            if not (isinstance(name, str) and NAME.match(name)
                    and module_path(kind, name).is_file())]


def _line(s) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def problems(man: dict, raw_size: int = 0) -> list:
    """Every breach of the benchmark's rules, as sentences."""
    out = []
    if raw_size > 64 * 1024:
        out.append("BENCHMARK.json is over 64 KiB")
    if set(man) != TOP:
        out.append(f"top-level keys {sorted(man)} != {sorted(TOP)}")
        return out
    paths = man["paths"]
    if not (1 <= len(paths) <= 16) or not all(
            isinstance(p, str) and PATH.match(p) and not p.startswith("/")
            and ".." not in p.split("/") for p in paths):
        out.append(f"bad paths {paths}")
    cmd = man["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_line(c) for c in cmd)):
        out.append("command must be a list of 1-32 one-line words")
    if not (isinstance(man["run_seconds"], int)
            and 1 <= man["run_seconds"] <= 51):
        out.append("run_seconds must be a whole number from 1 to 51")

    names = {}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in man[kind]:
            n = e.get("name", "")
            if not NAME.match(n):
                out.append(f"{kind}: bad name {n!r}")
            if n in names.get(kind, set()):
                out.append(f"{kind}: duplicate name {n!r}")
            names.setdefault(kind, set()).add(n)
    metric_names = names.get("end_to_end", set()) | names.get("per_layer",
                                                              set())
    if len(metric_names) != len(man["end_to_end"]) + len(man["per_layer"]):
        out.append("a name is used by two metrics")

    for c in man["configs"]:
        if set(c) != CONFIG_KEYS:
            out.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        if not _line(c["source"]) or not _line(c["why"]):
            out.append(f"config {c['name']}: source/why not one line")
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in paths):
            out.append(f"config {c['name']}: file outside paths")
        elif not (ROOT / c["file"]).is_file():
            out.append(f"config {c['name']}: no file {c['file']}")
        if len(c["reduced"]) > 16 or not all(NAME.match(k)
                                             for k in c["reduced"]):
            out.append(f"config {c['name']}: bad reduced {c['reduced']}")
    files = [c["file"] for c in man["configs"]]
    if len(set(files)) != len(files):
        out.append("two configurations share a file")

    cells = man["workloads"]
    if not 1 <= len(cells) <= 24:
        out.append("1 to 24 workloads")
    pairs = set()
    for w in cells:
        if set(w) != CELL_KEYS:
            out.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        if w["config"] not in names.get("configs", set()):
            out.append(f"workload {w['name']}: unknown config")
        if not NAME.match(w["traffic"]):
            out.append(f"workload {w['name']}: bad traffic name")
        elif not traffic_path(w["traffic"]).is_file():
            out.append(f"workload {w['name']}: no traffic file")
        elif w["config"] in names.get("configs", set()):
            try:
                out.extend(f"workload {w['name']}: {p}"
                           for p in cell_problems(cell(man, w["name"])))
            except (OSError, ValueError, KeyError) as e:
                out.append(f"workload {w['name']}: {e}")
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips must be 1 or 4")
        if not _line(w["why"]):
            out.append(f"workload {w['name']}: why not one line of <=200")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"workload {w['name']}: config and traffic repeat")
        pairs.add((w["config"], w["traffic"]))
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 2):
        out.append(f"{four} cells ask for 4 chips")
    used = {w.get("config") for w in cells}
    for c in man["configs"]:
        if c["name"] not in used:
            out.append(f"config {c['name']} is used by no cell")

    cell_names = {w["name"] for w in cells}
    for kind, keys in (("end_to_end", E2E_KEYS), ("per_layer", LAYER_KEYS)):
        for m in man[kind]:
            extra = set(m) - keys - {"workloads"}
            if set(m) - {"workloads"} != keys or extra:
                out.append(f"{kind} {m.get('name')}: keys {sorted(m)}")
                continue
            if not UNIT.match(m["unit"]):
                out.append(f"{m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"{m['name']}: better must be lower or higher")
            ok = E2E_SOURCES if kind == "end_to_end" else SOURCES
            if m["source"] not in ok:
                out.append(f"{m['name']}: source {m['source']!r}")
            if not set(m.get("workloads", [])) <= cell_names:
                out.append(f"{m['name']}: unknown workloads")
            if not reader_path(m["name"]).is_file():
                out.append(f"{m['name']}: no reader {reader_path(m['name'])}")
            if kind == "end_to_end":
                b = m["bound"]
                cap = 0.25
                if not (isinstance(b, (int, float)) and 0.01 <= b <= cap):
                    out.append(f"{m['name']}: bound {b} outside [0.01, 0.25]")
            elif not _line(m["layer"]):
                out.append(f"{m['name']}: layer not one line")

    e2e = {m["name"]: m for m in man["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("no setup_s metric")
    for m in man["per_layer"]:
        if m.get("moves") not in e2e:
            out.append(f"{m['name']}: moves unknown metric {m.get('moves')}")
            continue
        for w in m.get("workloads", sorted(cell_names)):
            if not any(x["name"] == m["moves"]
                       for x in metrics_of(man, w, "end_to_end")):
                out.append(f"{m['name']}: cell {w} does not report "
                           f"{m['moves']}")
    for w in sorted(cell_names):
        mine = {x["name"] for x in metrics_of(man, w, "end_to_end")}
        if "setup_s" not in mine or len(mine) < 2:
            out.append(f"cell {w}: needs setup_s and another end-to-end "
                       f"metric")
        if not metrics_of(man, w, "per_layer"):
            out.append(f"cell {w}: no per-layer metric")
    return out
