"""End-to-end training: loss decreases on learnable synthetic data."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ShapeSpec
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_train_step
from repro.models import build_model
from repro.optim import adamw


@pytest.mark.parametrize("arch", ["minitron_4b", "mamba2_370m"])
def test_loss_decreases(arch):
    """Overfit-one-batch: the canonical learning-dynamics sanity check."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    mesh = make_host_mesh(1, 1)
    shape = ShapeSpec("t", 32, 4, "train")
    bundle = build_train_step(cfg, mesh, shape, lr=3e-3, warmup_steps=10)
    rng = np.random.default_rng(0)
    seq = rng.integers(0, cfg.vocab, size=(4, 33)).astype(np.int32)
    batch = {"tokens": jnp.asarray(seq[:, :-1]),
             "targets": jnp.asarray(seq[:, 1:])}
    with mesh:
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw.init(params)
        step = bundle.jitted()
        losses = []
        for _ in range(40):
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.55, \
        losses[:3] + losses[-3:]


def test_microbatched_step_matches_plain():
    import dataclasses

    cfg = get_smoke_config("minitron_4b")
    model = build_model(cfg)
    mesh = make_host_mesh(1, 1)
    shape = ShapeSpec("t", 16, 8, "train")
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, cfg.vocab),
    }
    with mesh:
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw.init(params)
        b1 = build_train_step(cfg, mesh, shape)
        p1, _, m1 = b1.jitted()(params, opt, batch)
        cfg4 = dataclasses.replace(cfg, microbatches=4)
        b4 = build_train_step(cfg4, mesh, shape)
        p4, _, m4 = b4.jitted()(model.init(jax.random.PRNGKey(0)),
                                adamw.init(params), batch)
    # same data, same update (up to accumulation-order rounding)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=3e-3)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-3, atol=5e-3)


def test_data_pipeline_and_prefetch():
    from repro.configs import get_smoke_config
    from repro.data.pipeline import Prefetcher, SyntheticLM, host_batch_slice

    src = SyntheticLM(vocab=97, batch=4, seq_len=16, seed=1)
    pf = Prefetcher(src, depth=2)
    b = next(iter(pf))
    assert b["tokens"].shape == (4, 16)
    assert (b["targets"][:, :-1] == b["tokens"][:, 1:]).all()
    assert b["tokens"].max() < 97
    sl = host_batch_slice(256, host_id=3, num_hosts=16)
    assert sl == slice(48, 64)


def test_melt_augmentation_in_pipeline():
    """The paper's filters run as batch augmentation (data/augment.py)."""
    from repro.data import augment

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(3, 12, 12).astype(np.float32))
    out = augment.denoise_batch(x, op_size=3, sigma_d=1.0, sigma_r=0.5)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.var(out)) < float(jnp.var(x))
    boosted = augment.keypoint_boost(x[0])
    assert boosted.shape == x[0].shape
