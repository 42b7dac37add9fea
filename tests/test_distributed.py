"""Distributed melt engine + sharding rules + distributed train equivalence."""
import numpy as np
import pytest

from conftest import run_with_devices


def test_distributed_stencil_matches_single():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import gaussian_weights, apply_stencil
from repro.core.distributed import distributed_stencil

mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.asarray(np.random.RandomState(0).randn(16, 9, 5).astype(np.float32))
w = gaussian_weights((3, 3, 3), 1.2)
ref = apply_stencil(x, (3, 3, 3), w, method="materialize")
for pad in (0.0, "edge"):
    ref_p = apply_stencil(x, (3,3,3), w, method="materialize", pad_value=pad)
    out = distributed_stencil(x, mesh, "data", (3, 3, 3), w,
                              method="materialize", pad_value=pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_p),
                               rtol=1e-4, atol=1e-6)
print("dist-stencil OK")
""", 4)
    assert "dist-stencil OK" in out


def test_distributed_train_step_matches_single_device():
    """The FULL train step (loss+grads+AdamW) on a 2×2 mesh must equal the
    unsharded single-device step — the end-to-end SPMD correctness gate."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.configs.base import ShapeSpec
from repro.launch.steps import build_train_step
from repro.models import build_model
from repro.optim import adamw

cfg = get_smoke_config("minitron_4b")
model = build_model(cfg)
shape = ShapeSpec("t", 32, 4, "train")
batch = {
  "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab),
  "targets": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab),
}

# single device reference
params0 = model.init(jax.random.PRNGKey(0))
opt0 = adamw.init(params0)
mesh1 = jax.make_mesh((1, 1), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,)*2)
b1 = build_train_step(cfg, mesh1, shape)
with mesh1:
    p1, o1, m1 = b1.jitted()(params0, opt0, batch)

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
b2 = build_train_step(cfg, mesh, shape)
with mesh:
    params = jax.device_put(model.init(jax.random.PRNGKey(0)), b2.in_shardings[0])
    opt = jax.device_put(adamw.init(params), b2.in_shardings[1])
    bb = {k: jax.device_put(v, b2.in_shardings[2][k]) for k, v in batch.items()}
    p2, o2, m2 = b2.jitted()(params, opt, bb)

np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=2e-3)
l1 = jax.tree.leaves(p1); l2 = jax.tree.leaves(p2)
for a, b in zip(l1, l2):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=3e-3, atol=3e-3)
print("dist-train OK", float(m1["loss"]))
""", 4)
    assert "dist-train OK" in out


def test_serve_step_runs_sharded():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.configs.base import ShapeSpec
from repro.launch.steps import build_serve_step
from repro.models import build_model

cfg = get_smoke_config("minitron_4b")
model = build_model(cfg)
shape = ShapeSpec("d", 64, 4, "decode")
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
b = build_serve_step(cfg, mesh, shape)
with mesh:
    params = jax.device_put(model.init(jax.random.PRNGKey(0)), b.in_shardings[0])
    caches = jax.device_put(model.init_caches(4, 64), b.in_shardings[3])
    tok = jnp.zeros((4,), jnp.int32)
    pos = jnp.full((4,), 10, jnp.int32)
    logits, caches = b.jitted()(params, tok, pos, caches, {})
assert logits.shape == (4, cfg.vocab)
assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
print("serve OK")
""", 4)
    assert "serve OK" in out


def test_axis_rules_fallbacks():
    """Rules planner: DP-folding for ≤40B when batch divides; TP when heads
    divide and DP-folding is unavailable; SP fallback; EP vs expert-TP."""
    out = run_with_devices("""
import jax
from repro.configs import get_config
from repro.parallel.sharding import axis_rules_for

mesh = jax.make_mesh((2, 8), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
# phi4 (≤40B) with divisible batch → model folded into DP, no TP claims
r = axis_rules_for(get_config("phi4_mini_3p8b"), mesh, "train", 256, 4096)
assert r.table["batch"] == ("data", "model")
assert r.table["heads"] is None and r.table["ff"] is None
# phi4 with an indivisible batch (B=24 % 16 ≠ 0) → classic TP (24 heads / 8)
r = axis_rules_for(get_config("phi4_mini_3p8b"), mesh, "train", 24, 4096)
assert r.table["batch"] == ("data",)
assert r.table["heads"] == "model" and r.table["seq_act"] is None
# coder (33B ≤ 40B, 56 heads % 8 == 0): indivisible batch → TP applies
r = axis_rules_for(get_config("deepseek_coder_33b"), mesh, "train", 24, 4096)
assert r.table["heads"] == "model"
# hymba with indivisible batch: 25 heads → SP fallback
r = axis_rules_for(get_config("hymba_1p5b"), mesh, "train", 24, 4096)
assert r.table["heads"] is None and r.table["seq_act"] == "model"
# grok (314B — never DP-folded): 8 experts on 8-way model → EP
r = axis_rules_for(get_config("grok1_314b"), mesh, "train", 256, 4096)
assert r.table["batch"] == ("data",)
assert r.table["expert"] == "model"
# deepseek-v2: 160 % 8 == 0 → EP
r = axis_rules_for(get_config("deepseek_v2_236b"), mesh, "train", 256, 4096)
assert r.table["expert"] == "model"
print("rules OK")
""", 16)
    assert "rules OK" in out
