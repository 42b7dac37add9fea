#!/usr/bin/env python3
"""Run the analytics engine's default path once on a TPU, and check it.

Usage, from the root of a checkout on a machine with a TPU::

    python chip_smoke.py [--seed 0]     # one chip: kernels, pipe, tiled, serve
    python chip_smoke.py --chips 4      # four chips: the sharded paths only

Every phase calls the entry points a user calls, on the default
``method="auto"`` — on a TPU that is ``fused``, the compiled Pallas
kernels — and compares the result with the same graph on
``method="lax"`` run on the host's CPU in float32.  The reference does
not run on the chip: there XLA convolves float32 in bf16 passes unless
asked for ``highest`` precision, lays a CT-size convolution with 3 or 12
output channels out at 32 GiB of the chip's 16, and takes minutes to
compile each one.  On the host it is exact float32 and independent of
the chip's compiler.

One chip, at the sizes users of medical volume analytics process:

- kernels — each main-path kernel family (stencil, bank, depthwise,
  moment) compiled at the CT size; its HLO must hold ``tpu_custom_call``,
  the proof that the chip runs compiled kernels, not the interpreter;
- pipe  — ``Pipe.run`` over a CT study (256 slices of 512×512, int16
  Hounsfield units as float32): the README's 'same' gaussian→gradient→
  variance graph, its composed 'valid' twin, and
  ``filters.gaussian_curvature`` (the K=12 rank-3 bank, array output);
- tiled — ``TiledProgram.run`` of the composed graph over a host-resident
  512³ float32 volume with a 64 MiB tile budget;
- serve — a ``PipeService`` answering 16 BraTS-shaped (155×240×240)
  requests from two tenants through one registered program.

``--chips 4`` runs ``sharded_pipe_fn`` over the CT study split in slabs
on a 4-device ``"data"`` axis, and the tiled phase on a 4-device
``"tiles"`` mesh; each is compared with the same graph run whole on the
host.

All data is made from ``--seed``.  Each check prints its cold (compile
included) and warm wall time and its error against its tolerance.  The
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
it is printed only when every check passed.  The exit code is nonzero
when JAX finds no TPU, when a phase raises, or when a check fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
# the references run on the host's CPU, so its backend loads beside any
# accelerator the environment names
_PLATFORMS = os.environ.get("JAX_PLATFORMS", "")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import filters  # noqa: E402
from repro.core.filters import curvature_bank  # noqa: E402
from repro.core.plan import resolve_method  # noqa: E402
from repro.pipe import pipe  # noqa: E402
from repro.runtime.compile_cache import place_compile_cache  # noqa: E402
from repro.serve import PipeService, ServeConfig  # noqa: E402

#: a CT study: 512×512 slices (DICOM CT, the LIDC-IDRI collection); the
#: 256-slice count is assumed
CT = (256, 512, 512)
#: the host-resident volume of the tiled phase (512 MiB of float32)
TILED = (512, 512, 512)
TILE_BUDGET = 64 << 20
#: one BraTS volume (240×240×155 voxels, slices first here)
BRATS = (155, 240, 240)
REQUESTS = 16
MAX_BATCH = 4
#: every coalescing window fills before it closes, so one batch shape
#: (``MAX_BATCH``) compiles
MAX_WAIT_MS = 1000.0

#: Tolerance, as an error normalized to the reference's own scale
#: (:func:`array_error`, :func:`moment_error`).  Both paths multiply and
#: add in float32 — the kernels on the chip's vector unit, the reference
#: on the host — but sum up to 7³ taps of a composed stencil, then a
#: reduction over every voxel, in different orders, so rounding alone can
#: reach n·eps ≈ 1e-4 of the scale.  A wrong offset, halo or tile edge
#: moves whole voxels and shows as an error of order one.
TOL = 1e-4

FAMILIES = ("stencil", "bank", "depthwise", "moment")


@dataclasses.dataclass
class Check:
    name: str
    err: float
    tol: float
    cold_s: float = float("nan")
    warm_s: float = float("nan")
    note: str = ""

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.err) and self.err <= self.tol)

    def line(self) -> str:
        return (f"{'PASS' if self.ok else 'FAIL'} {self.name}: "
                f"cold {self.cold_s:.3f} s, warm {self.warm_s:.3f} s, "
                f"max err {self.err:.3e} (tol {self.tol:.0e})"
                + (f"; {self.note}" if self.note else ""))


# -- data --------------------------------------------------------------------


def _phantom(shape, seed, inside, rim, outside, noise):
    """A body ellipse in every slice with a denser rim (skull or bone)
    and Gaussian noise, made on the device."""

    def make(key):
        _, y, x = jnp.meshgrid(*(jnp.linspace(-1.0, 1.0, n) for n in shape),
                               indexing="ij")
        r2 = (y / 0.85) ** 2 + (x / 0.75) ** 2
        v = jnp.where(r2 < 1.0, jnp.where(r2 > 0.8, rim, inside), outside)
        return v + noise * jax.random.normal(key, shape, jnp.float32)

    return jax.jit(make)(jax.random.PRNGKey(seed))


def ct_study(shape, seed):
    """Hounsfield units — air −1000, soft tissue 40, bone 700 — rounded
    to int16 as a scanner stores them, then cast to float32."""
    hu = _phantom(shape, seed, 40.0, 700.0, -1000.0, 20.0)
    return jnp.clip(jnp.round(hu), -1024, 3071).astype(jnp.int16).astype(
        jnp.float32)


def mri_volume(shape, seed):
    """An MRI-like float32 volume: brain 800, rim 300, background 0."""
    return _phantom(shape, seed, 800.0, 300.0, 0.0, 30.0)


# -- comparison --------------------------------------------------------------


def array_error(out, ref) -> float:
    """max |out − ref| over max |ref|, in float64 on the host."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape:
        return float("inf")
    scale = max(float(np.max(np.abs(ref))), np.finfo(np.float32).tiny)
    return float(np.max(np.abs(out - ref)) / scale)


def moment_error(st, ref) -> float:
    """The larger of the mean's error in units of the reference's
    standard deviation and the variance's relative error, per channel;
    infinite if the element counts differ."""
    st, ref = jax.device_get((st, ref))
    if not np.array_equal(np.asarray(st.count), np.asarray(ref.count)):
        return float("inf")
    mean, rmean = (np.asarray(v, np.float64) for v in (st.mean, ref.mean))
    var, rvar = (np.asarray(v, np.float64)
                 for v in (st.variance, ref.variance))
    rvar = np.maximum(rvar, np.finfo(np.float32).tiny)
    return float(max(np.max(np.abs(mean - rmean) / np.sqrt(rvar)),
                     np.max(np.abs(var - rvar) / rvar)))


def host():
    """The host's CPU device, where the references run."""
    return jax.devices("cpu")[0]


def on_host(x):
    return jax.device_put(x, host())


def _timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _check(name, run, reference, compare, tol, note=""):
    """Run ``run`` twice (cold, then warm), ``reference`` once with the
    host's CPU as the default device, and compare."""
    _, cold = _timed(run)
    out, warm = _timed(run)
    with jax.default_device(host()):
        ref = jax.block_until_ready(reference())
    return Check(name, compare(out, ref), tol, cold, warm, note)


# -- graphs ------------------------------------------------------------------


def same_graph(x):
    """README's graph; 'same' padding, so it plans as a SplitStep."""
    return pipe(x).gaussian(1.5).gradient().moments(order=2)


def composed_graph(x):
    """The 'valid' chain the planner composes into one pass."""
    return (pipe(x).gaussian(1.5, op_shape=5, padding="valid")
            .gradient(padding="valid").moments(order=2))


# -- phases ------------------------------------------------------------------


def kernel_families(shape, tile_rows: int = 256) -> dict:
    """Compile each main-path kernel family for the default device at
    ``shape``; return ``{family: (has tpu_custom_call, bytes ratio)}``
    where the ratio is argument + output + temp bytes over the call's
    own input and output bytes (a 128-lane relayout would be ~128)."""
    from repro.core.grid import make_quasi_grid
    from repro.kernels import ops

    def f32(s):
        return jax.ShapeDtypeStruct(s, jnp.float32)

    g3 = make_quasi_grid(shape, (3,) * 3, 1, "same", 1)
    g1 = make_quasi_grid(shape, (1, 7, 1), 1, "valid", 1)
    K = curvature_bank(3).shape[1]
    rows = (3, int(np.prod(shape[:-1])), shape[-1])
    calls = {
        "stencil": (ops.fused_stencil.lower(
            f32(shape), grid=g3, weights=f32((27,)), pad_value="edge",
            tile_rows=tile_rows), shape, shape),
        "bank": (ops.fused_stencil_bank.lower(
            f32(shape), grid=g3, weight_matrix=f32((27, K)),
            pad_value="edge", tile_rows=tile_rows), shape, shape + (K,)),
        "depthwise": (ops.fused_stencil_depthwise.lower(
            f32(shape + (3,)), grid=g1, weights=f32((7, 3)), pad_value=0.0,
            tile_rows=tile_rows), shape + (3,), g1.out_shape + (3,)),
        "moment": (ops.fused_moment_sums.lower(f32(rows), order=2), rows,
                   (3,)),
    }
    found = {}
    for fam, (lowered, s_in, s_out) in calls.items():
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        own = 4 * (int(np.prod(s_in)) + int(np.prod(s_out)))
        ratio = (float("nan") if m is None else
                 (m.argument_size_in_bytes + m.output_size_in_bytes
                  + m.temp_size_in_bytes) / own)
        found[fam] = ("tpu_custom_call" in compiled.as_text(), ratio)
    return found


def phase_pipe(x):
    """``Pipe.run`` over one volume: three graphs on ``auto`` vs ``lax``."""
    xh = on_host(x)
    yield _check(
        "pipe/same-split", lambda: same_graph(x).run(pad_value="edge"),
        lambda: same_graph(xh).run(method="lax", pad_value="edge"),
        moment_error, TOL)
    yield _check(
        "pipe/valid-composed", lambda: composed_graph(x).run(),
        lambda: composed_graph(xh).run(method="lax"), moment_error, TOL)
    yield _check(
        "pipe/gaussian-curvature", lambda: filters.gaussian_curvature(x),
        lambda: filters.gaussian_curvature(xh, method="lax"), array_error,
        TOL)


def phase_tiled(vol: np.ndarray, budget: int, mesh=None, axis_name=None,
                name="tiled/valid-composed"):
    """``TiledProgram.run`` of the composed graph over a host volume,
    against ``Pipe.run`` of the whole volume on ``lax``."""
    tp = composed_graph(vol).plan_tiled(memory_budget=budget)
    yield _check(
        name, lambda: tp.run(mesh=mesh, axis_name=axis_name),
        lambda: composed_graph(on_host(vol)).run(method="lax"),
        moment_error, TOL,
        note=f"{tp.num_tiles} tiles in {tp.num_classes} classes")


def phase_serve(shape, requests: int, max_batch: int, seed: int,
                max_wait_ms: float = MAX_WAIT_MS):
    """A ``PipeService`` answering ``requests`` submissions from two
    tenants through one registered gaussian→gradient program."""
    xs = [mri_volume(shape, seed + i) for i in range(requests)]
    cfg = ServeConfig(max_batch=max_batch, max_wait_ms=max_wait_ms)
    with PipeService(cfg) as svc:
        prog = svc.register(pipe(xs[0]).gaussian(1.5).gradient())

        def serve_all():
            tickets = [prog.submit(x, tenant=("alice", "bob")[i % 2])
                       for i, x in enumerate(xs)]
            return [t.result() for t in tickets]

        _, cold = _timed(serve_all)
        outs, warm = _timed(serve_all)
    # one response per batch, each from a different position in its
    # batch and both tenants: a mis-stacked or mis-sliced batch shows in
    # each of them, at a quarter of the host's reference time
    sample = range(0, requests, max_batch + 1)
    err = 0.0
    with jax.default_device(host()):
        for i in sample:
            ref = pipe(on_host(xs[i])).gaussian(1.5).gradient().run(
                method="lax")
            err = max(err, array_error(outs[i], ref))
    yield Check("serve/gaussian-gradient", err, TOL, cold, warm,
                note=f"{requests} requests, 2 tenants, max_batch "
                     f"{max_batch}, responses {list(sample)} checked")


def phase_sharded(x, mesh):
    """``sharded_pipe_fn`` over slabs on the mesh's ``"data"`` axis vs
    ``Pipe.run`` of the same graph on one device."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.distributed import sharded_pipe_fn

    template = jax.ShapeDtypeStruct(x.shape, x.dtype)
    fn = jax.jit(sharded_pipe_fn(mesh, "data", same_graph(template),
                                 pad_value="edge"))
    xs = jax.device_put(x, NamedSharding(mesh, PartitionSpec("data")))
    xh = on_host(x)
    yield _check(
        "sharded/same", lambda: fn(xs),
        lambda: same_graph(xh).run(method="lax", pad_value="edge"),
        moment_error, TOL, note=f"{mesh.shape['data']} slabs")


# -- main --------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the paths that span four chips")
    args = ap.parse_args(argv)

    place_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    method = resolve_method("auto")
    print(f"device: {kind} x{len(devices)}; jax {jax.__version__}; "
          f"resolve_method('auto') = {method!r}")
    failed = [] if method == "fused" else ["auto is not fused"]
    checks = []

    def phase_kernels():
        found = kernel_families(CT)
        for fam in FAMILIES:
            has, ratio = found[fam]
            print(f"kernel {fam}: tpu_custom_call {has}, "
                  f"(args+out+temp)/own bytes {ratio:.3f}")
        missing = [f for f in FAMILIES if not found[f][0]]
        if missing:
            raise RuntimeError(f"no compiled kernel for {missing}")
        return ()

    seed = args.seed
    if args.chips == 1:
        phases = [
            ("kernels", phase_kernels),
            ("pipe", lambda: phase_pipe(ct_study(CT, seed))),
            ("tiled", lambda: phase_tiled(
                np.asarray(ct_study(TILED, seed + 1)), TILE_BUDGET)),
            ("serve", lambda: phase_serve(BRATS, REQUESTS, MAX_BATCH,
                                          seed + 2)),
        ]
    else:
        from jax.sharding import Mesh

        four = np.array(devices[:4])
        phases = [
            ("sharded", lambda: phase_sharded(ct_study(CT, seed),
                                              Mesh(four, ("data",)))),
            ("tiled-mesh", lambda: phase_tiled(
                np.asarray(ct_study(TILED, seed + 1)), TILE_BUDGET,
                mesh=Mesh(four, ("tiles",)), axis_name="tiles",
                name="tiled-mesh/valid-composed")),
        ]

    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            for c in phase():
                print(c.line(), flush=True)
                checks.append(c)
        except Exception:  # noqa: BLE001 — reported, then exit 1
            traceback.print_exc()
            failed.append(name)
        print(f"phase {name}: {time.perf_counter() - t0:.3f} s", flush=True)

    failed += [c.name for c in checks if not c.ok]
    if failed:
        print(f"chip_smoke: FAILED {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
