"""The set-up metrics the program counts itself (``trace_s.setup``,
``lower_s.setup``, ``compile_s.setup``, ``cache_misses.setup``,
``tune_s.setup``): each cell, run at a small size on the CPU in a
process of its own (the registry counts from the process's start, as in
a benchmark run), reports all five, and the seconds among them add up
to no more than the host clock's warm-up."""
import json
import math
import os
import subprocess
import sys

import pytest

from bench import manifest

ROOT = manifest.ROOT
MAN = manifest.load()
CELLS = [w["name"] for w in MAN["workloads"]]
NEW = ("trace_s.setup", "lower_s.setup", "compile_s.setup",
       "cache_misses.setup", "tune_s.setup")

RUN = """
import json, sys
sys.path[:0] = [{src!r}, {root!r}]
import jax
from bench import harness, manifest

man = manifest.load()
rec = harness.run_cell(manifest.cell(man, {cell!r}), 2 ** 32 + 77, 0.3,
                       shape=(16, 24, 40), devices=jax.devices()[:1])
print(json.dumps({{"metrics": harness.read_metrics(man, rec, "per_layer"),
                  "warmup_s": rec.setup_split["warmup_s"]}}))
"""


def test_manifest_is_sound():
    assert manifest.problems(MAN) == []
    names = [m["name"] for m in MAN["per_layer"]]
    assert all(n in names for n in NEW)
    for m in MAN["per_layer"]:
        if m["name"] in NEW:
            assert "workloads" not in m and m["moves"] == "setup_s"
            assert m["layer"] == "planner and compile"


@pytest.mark.parametrize("name", CELLS)
def test_setup_split_read_from_the_program(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    code = RUN.format(root=str(ROOT), src=str(ROOT / "src"), cell=name)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    got = {k: r["metrics"][k]["value"] for k in NEW}
    assert all(math.isfinite(v) and v >= 0 for v in got.values()), got
    assert got["compile_s.setup"] > 0, got
    spent = sum(got[k] for k in NEW if k.endswith("_s.setup"))
    assert spent <= r["warmup_s"], (got, r["warmup_s"])
