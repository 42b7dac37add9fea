"""A cell is added by files and entries alone: in a copy of the benchmark,
new files for a configuration and its data maker, a program entry, a
reference stage, a padding and two traffic mixes, plus their entries in
``BENCHMARK.json``, give two cells that run, prove correct and fail
their control, with no existing file edited."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

from bench import manifest

ROOT = manifest.ROOT

FILES = {
    "bench/configs/ramp-small.json": json.dumps({
        "name": "ramp-small", "source": "https://example.org/ramp",
        "axes": ["z", "y", "x"], "z": 12, "y": 16, "x": 24,
        "dtype": "float32",
        "maker": {"kind": "ramp", "a": 0.5, "b": 0.25, "noise": 1.0},
        "reduced": []}),
    "bench/makers/ramp.py": '''
        import jax
        import jax.numpy as jnp


        def rows(p, shape, key, rows):
            _, Y, X = shape
            z = rows.astype(jnp.float32)[:, None, None]
            y = jnp.arange(Y, dtype=jnp.float32)[None, :, None]
            x = jnp.arange(X, dtype=jnp.float32)[None, None, :]
            noise = jax.vmap(lambda r: jax.random.normal(
                jax.random.fold_in(key, r), (Y, X), jnp.float32))(rows)
            return p["a"] * z * z + p["b"] * y * x + p["noise"] * noise
        ''',
    "bench/entries/filters_hessian.py": '''
        def build(loop):
            from repro.core import filters

            pad = loop.pad
            return lambda x: filters.hessian(x, pad_value=pad).reshape(
                x.shape + (9,))
        ''',
    "bench/stages/hessian.py": '''
        import jax.numpy as jnp

        from bench.reference import tap


        def radius(kw):
            return 1


        def channels(c_in, kw):
            return 9 * c_in


        def ops(kw, c_in):
            return c_in * 9 * 4


        def apply(vp, r, kw, dtype):
            def f(*d):
                return tap(vp, r, *d)
            out = []
            for i in range(3):
                for j in range(3):
                    e = [0, 0, 0]
                    if i == j:
                        e[i] = 1
                        out.append(f(*e) + f(*(-a for a in e))
                                   - 2 * f(0, 0, 0))
                        continue
                    acc = 0
                    for si in (-1, 1):
                        for sj in (-1, 1):
                            e[i], e[j] = si, sj
                            acc = acc + 0.25 * si * sj * f(*e)
                    out.append(acc)
            return jnp.stack(out, axis=-1)
        ''',
    "bench/pads/valid.py": '''
        import jax.numpy as jnp


        def extent(n, r):
            return n - 2 * r, r


        def rows(v, lo, first, count, n_in):
            z = jnp.clip(jnp.arange(count, dtype=jnp.int32) + first, 0,
                         n_in - 1)
            return jnp.take(v, z - lo, axis=0)


        def plane(v, r):
            return v
        ''',
    "bench/traffic/ramp-hessian.json": json.dumps({
        "entry": "filters_hessian", "loop": "closed",
        "graph": [["hessian", {}]], "pad_value": "edge", "pool": 2,
        "result": "device_array", "limits": {"array_err": 1e-3}}),
    "bench/traffic/ct-valid-gauss.json": json.dumps({
        "entry": "pipe_run", "loop": "closed",
        "graph": [["gaussian", {"sigma": 1.0, "padding": "valid"}]],
        "pad_value": "edge", "pool": 2, "result": "device_array",
        "limits": {"array_err": 1e-3}}),
}

RUN = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    from bench import harness, manifest
    man = manifest.load()
    out = {{"problems": manifest.problems(man)}}
    for name, shape in (("ramp-hessian", None),
                        ("ct-valid-gauss", (16, 24, 40))):
        rec = harness.run_cell(manifest.cell(man, name), 2 ** 33 + 5, 0.3,
                               shape=shape, devices=jax.devices()[:1],
                               with_control=True)
        out[name] = {{"correct": rec.correct,
                      "control_fails": any(v > lim for v, lim in
                                           rec.control_checks.values()),
                      "calls": rec.window["calls"] > 0,
                      "checks": {{k: v for k, (v, _) in rec.checks.items()}}}}
    print(json.dumps(out))
""")


def _copy_with_new_cells(dst):
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for rel, text in FILES.items():
        path = dst / rel
        assert not path.exists(), f"{rel} is not new"
        path.write_text(textwrap.dedent(text).lstrip())
    man = manifest.load()
    man["configs"].append({
        "name": "ramp-small", "source": "https://example.org/ramp",
        "file": "bench/configs/ramp-small.json", "reduced": [],
        "why": "a quadratic ramp with noise"})
    for name, config in (("ramp-hessian", "ramp-small"),
                         ("ct-valid-gauss", "ct-lidc")):
        man["workloads"].append({"name": name, "config": config,
                                 "traffic": name, "chips": 1,
                                 "why": "added by files alone"})
        for m in man["end_to_end"] + man["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(name)
    (dst / "BENCHMARK.json").write_text(json.dumps(man, indent=2))


def test_cells_are_added_by_files_alone(tmp_path):
    _copy_with_new_cells(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    code = RUN.format(root=str(tmp_path), src=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out.pop("problems") == []
    for name, r in out.items():
        assert r["correct"] and r["control_fails"] and r["calls"], (name, r)
