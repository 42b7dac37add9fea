"""Pipeline parallelism: GPipe schedule == sequential stage application."""
from conftest import run_with_devices


def test_pipeline_matches_sequential():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.pipeline import pipeline_apply, sequential_reference

mesh = jax.make_mesh((4,), ("stage",), axis_types=(jax.sharding.AxisType.Auto,))
S, n_micro, mb, d = 4, 6, 2, 8
key = jax.random.PRNGKey(0)
W = jax.random.normal(key, (S, d, d)) * 0.3
b = jax.random.normal(jax.random.PRNGKey(1), (S, d)) * 0.1
params = {"w": W, "b": b}

def layer_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])

x = jax.random.normal(jax.random.PRNGKey(2), (n_micro, mb, d))
got = pipeline_apply(mesh, "stage", layer_fn, params, x)
want = sequential_reference(layer_fn, params, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
print("pipeline OK")
""", 4)
    assert "pipeline OK" in out
