"""Optimizer substrate: AdamW, schedules, error-feedback compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import adamw
from repro.optim.compression import dequantize_int8, quantize_int8
from repro.optim.schedule import warmup_cosine


def test_adamw_converges_on_quadratic():
    target = jnp.asarray([1.0, -2.0, 0.5])
    params = {"w": jnp.zeros(3)}
    state = adamw.init(params)

    @jax.jit
    def step(p, s):
        g = jax.grad(lambda q: jnp.sum((q["w"] - target) ** 2))(p)
        return adamw.update(g, s, p, lr=5e-2, weight_decay=0.0)

    for _ in range(300):
        params, state = step(params, state)
    np.testing.assert_allclose(params["w"], target, atol=1e-2)


def test_adamw_bf16_moments_still_converge():
    target = jnp.asarray([0.8, -0.3])
    params = {"w": jnp.zeros(2)}
    state = adamw.init(params, moment_dtype=jnp.bfloat16)
    assert state.mu["w"].dtype == jnp.bfloat16

    @jax.jit
    def step(p, s):
        g = jax.grad(lambda q: jnp.sum((q["w"] - target) ** 2))(p)
        return adamw.update(g, s, p, lr=5e-2, weight_decay=0.0)

    for _ in range(300):
        params, state = step(params, state)
    np.testing.assert_allclose(params["w"], target, atol=5e-2)


def test_grad_clip_bounds_update():
    params = {"w": jnp.zeros(4)}
    state = adamw.init(params)
    huge = {"w": jnp.full(4, 1e9)}
    p2, _ = adamw.update(huge, state, params, lr=1e-3, grad_clip=1.0,
                         weight_decay=0.0)
    assert float(jnp.max(jnp.abs(p2["w"]))) < 1e-2  # clip kept step sane


def test_schedule_shape():
    lr = warmup_cosine(1e-3, 100, 1000)
    assert float(lr(jnp.asarray(0))) == 0.0
    assert abs(float(lr(jnp.asarray(100))) - 1e-3) < 1e-9
    assert float(lr(jnp.asarray(550))) < 1e-3
    assert float(lr(jnp.asarray(1000))) >= 1e-4 * 0.9  # floor


def test_int8_quant_roundtrip_bound():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1000), jnp.float32)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(err.max()) <= float(s) * 0.5 + 1e-7


def test_compressed_psum_error_feedback_converges():
    """Mean of per-shard gradients via int8 EF-psum drives SGD to the same
    optimum as exact averaging (4 fake devices, shard_map)."""
    import subprocess
    from conftest import run_with_devices

    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.optim.compression import compressed_psum, init_error_state

mesh = jax.make_mesh((4,), ("d",), axis_types=(jax.sharding.AxisType.Auto,))
target = jnp.asarray([1.0, -2.0, 0.5, 3.0])

def local_grad(w, xs):
    # per-shard quadratic with different data => different local grads
    return 2 * (w - target) * xs

w = jnp.zeros(4)
err = jnp.zeros((4, 4))  # per-device error state (stacked)

@jax.jit
def step(w, err, key):
    xs = jax.random.uniform(key, (4, 4), minval=0.5, maxval=1.5)
    def shard_fn(w, x, e):
        g = local_grad(w, x[0])
        gm, e2 = compressed_psum(g, e[0], "d")
        return gm, e2[None]
    f = jax.shard_map(shard_fn, mesh=mesh,
                  in_specs=(P(), P("d", None), P("d", None)),
                  out_specs=(P(), P("d", None)), check_vma=False)
    g, err = f(w, xs, err)
    return w - 0.05 * g, err

for i in range(400):
    w, err = step(w, err, jax.random.PRNGKey(i))
np.testing.assert_allclose(np.asarray(w), np.asarray(target), atol=2e-2)
print("EF-int8 converged", w)
"""
    out = run_with_devices(code, 4)
    assert "EF-int8 converged" in out
