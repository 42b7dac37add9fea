"""Checkpointing + fault tolerance: atomic commits, restarts, elasticity."""
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from repro.checkpoint import checkpoint as ckpt
from repro.runtime.fault_tolerance import Heartbeat, StragglerMonitor, run_restartable


def _tree(key=0):
    k = jax.random.PRNGKey(key)
    return {"a": jax.random.normal(k, (8, 4)),
            "nested": {"b": jnp.arange(6).reshape(2, 3).astype(jnp.int32)},
            "scalars": jnp.float32(3.5)}


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 7, t)
    assert ckpt.latest_step(str(tmp_path)) == 7
    r = ckpt.restore(str(tmp_path), 7, t)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_uncommitted_checkpoint_ignored(tmp_path):
    t = _tree()
    path = ckpt.save(str(tmp_path), 3, t)
    ckpt.save(str(tmp_path), 5, t)
    os.remove(os.path.join(str(tmp_path), "step_000000005", "_COMMITTED"))
    assert ckpt.latest_step(str(tmp_path)) == 3  # 5 is torn → invisible


def test_async_save_completes(tmp_path):
    t = _tree()
    handle = ckpt.save(str(tmp_path), 11, t, async_=True)
    handle.join(timeout=30)
    assert ckpt.latest_step(str(tmp_path)) == 11


def test_restartable_driver_survives_crashes(tmp_path):
    """Inject failures at steps 7 and 13: driver must restore + finish."""
    crashes = {7: True, 13: True}
    seen = []

    def init_state():
        return {"w": jnp.zeros(2), "n": jnp.int32(0)}

    def step_fn(state, batch):
        n = int(state["n"])
        if crashes.pop(n + 1, None):
            raise RuntimeError(f"injected failure at step {n + 1}")
        return {"w": state["w"] + batch, "n": state["n"] + 1}

    def batches():
        while True:
            yield jnp.ones(2)

    state, monitor = run_restartable(
        step_fn, init_state, batches(), ckpt_dir=str(tmp_path),
        total_steps=20, save_every=5, max_restarts=5,
        on_step=lambda s, st, dt: seen.append(s),
    )
    assert int(state["n"]) == 20
    # w == n  (restart replays from last committed multiple of 5)
    np.testing.assert_allclose(np.asarray(state["w"]), [20.0, 20.0])
    assert not crashes  # both injected failures actually fired


def test_restart_bounded(tmp_path):
    def init_state():
        return {"n": jnp.int32(0)}

    def step_fn(state, batch):
        raise RuntimeError("always fails")

    def batches():
        while True:
            yield None

    with pytest.raises(RuntimeError):
        run_restartable(step_fn, init_state, batches(),
                        ckpt_dir=str(tmp_path), total_steps=5,
                        max_restarts=2)


def test_straggler_monitor_flags_outliers():
    m = StragglerMonitor(factor=2.0, window=20, warmup=3)
    for i in range(10):
        m.observe(i, 0.1)
    assert m.observe(10, 0.5)       # 5× median → flagged
    assert not m.observe(11, 0.12)  # normal
    assert len(m.flagged) == 1


def test_heartbeat_staleness(tmp_path):
    hb = Heartbeat(str(tmp_path), host_id=0)
    hb.beat(step=1)
    hb2 = Heartbeat(str(tmp_path), host_id=1)
    hb2.beat(step=1)
    assert hb.stale_hosts(2, timeout_s=60) == []
    assert hb.stale_hosts(3, timeout_s=60) == []  # host 2: startup grace
    hb._created -= hb.startup_grace_s + 1.0       # grace lapses
    assert hb.stale_hosts(3, timeout_s=60) == [2]  # host 2 never beat


def test_elastic_restore_across_meshes(tmp_path):
    """Save on 4 devices, restore on 2 and on 8 — training-equivalent."""
    from conftest import run_with_devices

    code = f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.checkpoint import checkpoint as ckpt

tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
d = "{tmp_path}"

mesh4 = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
sh4 = {{"w": NamedSharding(mesh4, P("data", None))}}
placed = jax.device_put(tree, sh4)
ckpt.save(d, 1, placed)

for n in (2, 8):
    mesh = jax.make_mesh((n,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    sh = {{"w": NamedSharding(mesh, P("data", None))}}
    r = ckpt.restore(d, 1, tree, sh)
    assert len(r["w"].sharding.device_set) == n
    np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(tree["w"]))
print("elastic OK")
"""
    out = run_with_devices(code, 8)
    assert "elastic OK" in out


def test_async_save_surfaces_writer_exceptions(tmp_path):
    """PR-7 audit: a failing writer thread must raise at join, not
    silently drop the error while the caller believes the step durable."""

    class Boom:
        """A pytree leaf whose device_get explodes mid-write."""

    def bad_get(x):
        raise OSError("disk full")

    t = {"a": jnp.ones(3)}
    handle = ckpt.save(str(tmp_path), 1, t, async_=True)
    handle.join(timeout=30)  # healthy save: join returns the final path

    import unittest.mock as mock
    with mock.patch.object(jax, "device_get", side_effect=bad_get):
        handle = ckpt.save(str(tmp_path), 2, t, async_=True)
        with pytest.raises(OSError, match="disk full"):
            handle.join(timeout=30)
    assert ckpt.latest_step(str(tmp_path)) == 1  # step 2 never committed
    # the failed writer's temp dir was cleaned up, not left to shadow
    assert not [d for d in os.listdir(str(tmp_path)) if ".tmp" in d]


def test_async_save_join_returns_final_path(tmp_path):
    t = _tree()
    handle = ckpt.save(str(tmp_path), 4, t, async_=True)
    final = handle.join(timeout=30)
    assert final == os.path.join(str(tmp_path), "step_000000004")
    assert handle.result() == final  # idempotent alias
    assert not handle.is_alive()


def test_concurrent_same_step_saves_do_not_race(tmp_path):
    """PR-7 audit: two concurrent saves of the same step must not
    interleave files in a shared temp dir — each stages privately and
    the committed checkpoint is one writer's complete tree."""
    import threading

    n_writers, errors = 6, []
    barrier = threading.Barrier(n_writers)

    def writer(i):
        try:
            barrier.wait(timeout=30)
            ckpt.save(str(tmp_path), 9, {"w": jnp.full((32, 32), float(i)),
                                         "tag": jnp.int32(i)})
        except Exception as e:  # noqa: BLE001 — collected for the assert
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errors
    assert ckpt.latest_step(str(tmp_path)) == 9
    r = ckpt.restore(str(tmp_path), 9, {"w": jnp.zeros((32, 32)),
                                        "tag": jnp.int32(0)})
    # a complete, self-consistent tree from ONE writer (no chimera)
    i = int(r["tag"])
    np.testing.assert_array_equal(np.asarray(r["w"]),
                                  np.full((32, 32), float(i)))
    assert not [d for d in os.listdir(str(tmp_path)) if ".tmp" in d]
