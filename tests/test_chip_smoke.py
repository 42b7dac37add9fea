"""chip_smoke.py, rehearsed on the CPU at a tiny size.

The script itself has no CPU mode: it refuses to run without a TPU.
Its phase functions take their sizes, so each runs here on volumes of a
few thousand voxels with the Pallas kernels in interpret mode.  On the
CPU ``method="auto"`` resolves to ``lax``, which would compare ``lax``
with itself, so the tests steer ``auto`` to ``fused`` — the choice a
TPU makes — and ``lax`` stays the reference.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from conftest import run_with_devices

ROOT = Path(__file__).resolve().parent.parent


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # its dataclass looks itself up here
    spec.loader.exec_module(mod)
    return mod


cs = _load_smoke()


@pytest.fixture
def auto_is_fused(monkeypatch):
    import repro.core.plan as plan

    resolve = plan.resolve_method
    monkeypatch.setattr(plan, "resolve_method",
                        lambda m: "fused" if m == "auto" else resolve(m))


def _assert_passed(checks, names):
    assert [c.name for c in checks] == names
    for c in checks:
        assert c.ok, c.line()
        assert c.cold_s > 0 and c.warm_s > 0


def test_pipe_phase_matches_lax(auto_is_fused):
    checks = list(cs.phase_pipe(cs.ct_study((12, 20, 20), 0)))
    _assert_passed(checks, ["pipe/same-split", "pipe/valid-composed",
                            "pipe/gaussian-curvature"])


def test_tiled_phase_matches_lax(auto_is_fused):
    vol = np.asarray(cs.ct_study((16, 20, 20), 1))
    checks = list(cs.phase_tiled(vol, 64 << 10))
    _assert_passed(checks, ["tiled/valid-composed"])
    assert int(checks[0].note.split()[0]) > 1  # really streamed tiles


def test_serve_phase_matches_lax(auto_is_fused):
    checks = list(cs.phase_serve((8, 12, 12), 4, 2, 2))
    _assert_passed(checks, ["serve/gaussian-gradient"])


def test_kernel_families_compile_every_family():
    found = cs.kernel_families((8, 16, 16), tile_rows=16)
    assert set(found) == set(cs.FAMILIES)
    for has, ratio in found.values():
        assert not has  # interpret mode on the CPU: no Mosaic kernel
        assert np.isfinite(ratio) and ratio > 0


def test_four_device_phases_match_one_device():
    out = run_with_devices(f"""
import sys
sys.path.insert(0, {str(ROOT)!r})
import numpy as np, jax
from jax.sharding import Mesh
import chip_smoke as cs
import repro.core.plan as plan
resolve = plan.resolve_method
plan.resolve_method = lambda m: "fused" if m == "auto" else resolve(m)
four = np.array(jax.devices()[:4])
checks = list(cs.phase_sharded(cs.ct_study((16, 20, 20), 0),
                               Mesh(four, ("data",))))
checks += list(cs.phase_tiled(np.asarray(cs.ct_study((16, 20, 20), 1)),
                              64 << 10, mesh=Mesh(four, ("tiles",)),
                              axis_name="tiles", name="tiled-mesh"))
for c in checks:
    print(c.line())
assert [c.name for c in checks] == ["sharded/same", "tiled-mesh"]
assert all(c.ok for c in checks)
print("four-device OK")
""")
    assert "four-device OK" in out


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_a_check_fails_beyond_its_tolerance_or_on_nan():
    assert cs.Check("x", cs.TOL / 2, cs.TOL).ok
    assert not cs.Check("x", 2 * cs.TOL, cs.TOL).ok
    assert not cs.Check("x", float("nan"), cs.TOL).ok


def test_compile_cache_placement(tmp_path):
    code = textwrap.dedent("""
        import jax
        from repro.runtime.compile_cache import place_compile_cache
        print(place_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def run(extra):
        out = subprocess.run([sys.executable, "-c", code],
                             env=dict(env, **extra), capture_output=True,
                             text=True, timeout=120, cwd=tmp_path)
        assert out.returncode == 0, out.stderr
        return out.stdout.split()

    fixed = str(ROOT / ".jax_cache")
    assert run({}) == [fixed, fixed]
    assert run({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}) == [
        str(tmp_path), str(tmp_path)]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_benchmark_run_exits_nonzero_on_a_failed_section(monkeypatch,
                                                         capsys, tmp_path):
    from benchmarks import paper_figs, run

    def boom():
        raise RuntimeError("section exploded")

    # a set cache directory keeps the helper off this process's config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(paper_figs, "fig7_abstraction_levels", boom)
    with pytest.raises(SystemExit) as e:
        run.main(["--sections", "fig7", "--json", str(tmp_path / "b.json")])
    assert e.value.code and "fig7" in str(e.value.code)
    assert "ERROR,0.0,section exploded" in capsys.readouterr().out
    rows = json.loads((tmp_path / "b.json").read_text())["rows"]
    assert [r["name"] for r in rows] == ["ERROR"]
