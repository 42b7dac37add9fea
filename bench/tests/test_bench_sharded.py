"""The four-chip cell ``rm-sharded-variance`` on four host devices of the
CPU, and its two readers.

The cell runs through the rest of a run at a small size, in a process of
its own (the device count is fixed when JAX starts): sound runs pass,
the bfloat16 control fails, and a slab that reads edge padding where its
neighbour's slices belong makes ``correct`` false.  The readers
``collective_share.sharded`` and ``halo_mb.sharded`` read a hand-made
trace and registry."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness, manifest, trace_reduce
from bench.trace_reduce import Trace

ROOT = manifest.ROOT
CELL = "rm-sharded-variance"

RUN = """
import json, sys
sys.path[:0] = [{src!r}, {root!r}]
import jax
from bench import harness, manifest
{patch}
rec = harness.run_cell(manifest.cell(manifest.load(), {cell!r}),
                       2 ** 40 + 3, 0.3, shape=(32, 64, 128),
                       devices=jax.devices()[:4], with_control={control})
print(json.dumps({{"correct": rec.correct, "chips": rec.chips,
                  "calls": rec.window["calls"],
                  "control": rec.control_checks}}))
"""

#: every slab reads edge padding on both sides, as if it stood at the
#: volume's edge: the exchange with its neighbours is left out
NO_EXCHANGE = """
import jax.numpy as jnp
from repro.core import distributed

def no_exchange(x, lo, hi, axis_name, pad_value=0.0, axis=0):
    parts = [distributed._edge_block(x, lo, axis, True, pad_value)] \\
        if lo else []
    parts.append(x)
    if hi:
        parts.append(distributed._edge_block(x, hi, axis, False, pad_value))
    return jnp.concatenate(parts, axis=axis)

distributed.halo_exchange = no_exchange
"""


def test_sharded_cell_resolves_to_its_files():
    man = manifest.load()
    spec = manifest.cell(man, CELL)
    cfg, traffic = spec["config"], spec["traffic"]
    assert manifest.cell_problems(spec) == []
    assert spec["cell"]["chips"] == 4
    assert all(isinstance(cfg[k], int) and cfg[k] > 0 for k in cfg["axes"])
    assert cfg["slices"] % spec["cell"]["chips"] == 0
    assert traffic["loop"] == "closed_mesh"
    closed = manifest.module("loops", "closed")
    assert issubclass(manifest.module("loops", "closed_mesh").Loop,
                      closed.Loop)
    assert traffic["limits"]["count_err"] == 0
    assert all(v >= 0 for v in traffic["limits"].values())
    conf = {c["name"]: c for c in man["configs"]}[spec["cell"]["config"]]
    assert cfg["name"] == conf["name"] == "rm-insitu"
    assert cfg["reduced"] == conf["reduced"] == ["slices"]


def _run(patch="", control=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    code = RUN.format(root=str(ROOT), src=str(ROOT / "src"), cell=CELL,
                      patch=patch, control=control)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_cell_is_correct_and_its_control_is_not():
    r = _run(control=True)
    assert r["correct"] and r["chips"] == 4 and r["calls"] > 0, r
    assert any(v > lim for v, lim in r["control"].values()), r["control"]


def test_slabs_that_skip_the_exchange_fail():
    r = _run(patch=NO_EXCHANGE)
    assert r["calls"] > 0 and not r["correct"], r


class _Run:
    """What a reader sees of a run: its trace and window."""

    def __init__(self, trace=None):
        self.trace, self.window = trace, {"calls": 3}


def _trace():
    mesh_ops = {
        0: [(0.0, 1.0, "fusion.1"), (1.0, 1.2, "collective-permute.3"),
            (1.5, 1.6, "all-gather.1")],
        1: [(0.0, 1.0, "fusion.1"), (1.0, 1.5, "collective-permute.3")],
    }
    spans = [(0.0, 2.0, "bench/window"), (0.0, 2.0, "bench/call")]
    return trace_reduce.reduce(Trace(mesh_ops, spans))


def test_collective_share_is_the_mean_chip_share_of_the_window():
    read = harness._reader("collective_share.sharded")
    # chip 0: 0.2 + 0.1 s, chip 1: 0.5 s, of a 2 s window
    assert read(_Run(_trace())) == pytest.approx(100.0 * 0.4 / 2.0)
    assert read(_Run()) is None


def test_halo_mb_reads_the_programs_gauge(monkeypatch):
    import repro.obs
    from repro.obs import MetricsRegistry

    read = harness._reader("halo_mb.sharded")
    reg = MetricsRegistry()
    monkeypatch.setattr(repro.obs, "REGISTRY", reg)
    assert read(_Run()) is None  # a program that keeps no such gauge
    reg.gauge("shard/halo_bytes").set(8 * 2048 * 2048 * 4)
    assert read(_Run()) == pytest.approx(134.217728)


def test_halo_mb_matches_the_cells_exchanges():
    """The gauge a build of the cell's graph sets: 3 + 3 Gaussian and
    1 + 1 gradient planes a chip, float32 (134.2 MB at 2048×2048)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.obs import REGISTRY

    spec = manifest.cell(manifest.load(), CELL)
    loop = manifest.module("loops", "closed_mesh")
    shape = (8, 16, 24)
    drv = loop.Loop(spec, 5, jax.devices()[:1], shape)
    drv.mesh = Mesh(np.array(drv.devices), ("data",))
    manifest.module("entries", spec["traffic"]["entry"]).build(drv)
    assert REGISTRY.snapshot()["shard/halo_bytes"] == 8 * 16 * 24 * 4
