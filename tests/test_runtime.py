"""The runtime layer: elastic re-meshing + fault-tolerance corners.

``repro.runtime.elastic`` had zero direct tests: it is the piece that
turns the checkpoint contract (unsharded leaves + shardings derived from
(config, mesh) at restore time) into elastic scaling — save on N devices,
``restore_elastic`` onto an M-device mesh and keep going.  Pinned here:

- ``replan`` plans a full NamedSharding tree for a real model config on a
  real mesh (shapes tree × param-axes tree, every leaf covered);
- ``restore_elastic`` round-trips values and re-places them on the new
  mesh, including device counts the checkpoint never saw (subprocess with
  fake host devices), with both a plain ``Mesh`` and the explicit
  axis-type ``make_mesh`` spelling;
- fault-tolerance corners the checkpoint suite leaves open: corrupt
  heartbeat files, heartbeat refresh, straggler warmup/median,
  KeyboardInterrupt passing straight through the crash-only driver, and
  resume-from-committed-step semantics.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from conftest import run_with_devices

from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.runtime.elastic import replan, restore_elastic
from repro.runtime.fault_tolerance import (
    Heartbeat,
    StragglerMonitor,
    run_restartable,
)

ARCH = "mamba2_370m"


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config(ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _host_mesh():
    return Mesh(np.array(jax.devices()).reshape(1, 1), ("data", "model"))


# -- elastic -----------------------------------------------------------------


def test_replan_covers_every_leaf(smoke_model):
    cfg, model, params = smoke_model
    shapes = jax.eval_shape(lambda: params)
    rules, shardings = replan(cfg, _host_mesh(), "train", 2, 32, shapes,
                              model.param_axes())
    p_leaves = jax.tree.leaves(params)
    s_leaves = jax.tree.leaves(shardings)
    assert len(s_leaves) == len(p_leaves)
    assert all(isinstance(s, NamedSharding) for s in s_leaves)
    # specs must be placeable for their leaf shapes (device_put validates)
    placed = jax.device_put(params, shardings)
    for a, b in zip(jax.tree.leaves(placed), p_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_elastic_roundtrip_values(smoke_model, tmp_path):
    cfg, model, params = smoke_model
    ckpt.save(str(tmp_path), 5, params)
    r = restore_elastic(str(tmp_path), 5, params, cfg, _host_mesh(),
                        "train", 2, 32, model.param_axes())
    for a, b in zip(jax.tree.leaves(r), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert isinstance(a.sharding, NamedSharding)


def test_restore_elastic_missing_step_raises(smoke_model, tmp_path):
    cfg, model, params = smoke_model
    with pytest.raises(FileNotFoundError):
        restore_elastic(str(tmp_path), 1, params, cfg, _host_mesh(),
                        "train", 2, 32, model.param_axes())


def test_restore_elastic_across_device_counts(tmp_path):
    """Save on a (2, 1) mesh, restore_elastic on (4, 1) and (1, 1) —
    values identical, placement follows the new mesh.  Plain ``Mesh``
    construction."""
    code = f"""
import jax, numpy as np
from jax.sharding import Mesh
from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.runtime.elastic import restore_elastic

cfg = get_smoke_config("{ARCH}")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
axes = model.param_axes()
d = "{tmp_path}"

# genuinely save MESH-SHARDED leaves: place on a (2, 1) mesh first, so
# the restore really re-shards a sharded save, not a host-only tree
from repro.runtime.elastic import replan
mesh2 = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("data", "model"))
shapes = jax.eval_shape(lambda: params)
_, sh2 = replan(cfg, mesh2, "train", 4, 32, shapes, axes)
placed = jax.device_put(params, sh2)
assert any(len(l.sharding.device_set) == 2 for l in jax.tree.leaves(placed))
ckpt.save(d, 1, placed)

for n in (4, 1):
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(n, 1),
                ("data", "model"))
    r = restore_elastic(d, 1, params, cfg, mesh, "train",
                        batch_size=4, seq_len=32, axes_tree=axes)
    for a, b in zip(jax.tree.leaves(r), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.sharding.mesh.devices.size == n
print("elastic re-mesh OK")
"""
    out = run_with_devices(code, 4)
    assert "elastic re-mesh OK" in out


def test_restore_elastic_explicit_axis_type_mesh(tmp_path):
    """The ``make_mesh`` + ``AxisType`` spelling of the same contract."""
    code = f"""
import jax, numpy as np
from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.runtime.elastic import restore_elastic

cfg = get_smoke_config("{ARCH}")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
ckpt.save("{tmp_path}", 1, params)
mesh = jax.make_mesh((4, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
r = restore_elastic("{tmp_path}", 1, params, cfg, mesh, "train",
                    batch_size=4, seq_len=32,
                    axes_tree=model.param_axes())
for a, b in zip(jax.tree.leaves(r), jax.tree.leaves(params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("axis-type elastic OK")
"""
    out = run_with_devices(code, 4)
    assert "axis-type elastic OK" in out


# -- fault tolerance: the corners test_checkpoint leaves open ----------------


def test_heartbeat_corrupt_file_counts_as_stale(tmp_path):
    hb = Heartbeat(str(tmp_path), host_id=0)
    hb.beat(step=1)
    with open(os.path.join(str(tmp_path), "host_1.hb"), "w") as f:
        f.write("{not json")
    assert hb.stale_hosts(2, timeout_s=60) == [1]


def test_heartbeat_refresh_unstales(tmp_path):
    hb = Heartbeat(str(tmp_path), host_id=0)
    path = os.path.join(str(tmp_path), "host_0.hb")
    with open(path, "w") as f:  # a beat far in the past
        json.dump({"t": 1.0, "step": 0}, f)
    assert hb.stale_hosts(1, timeout_s=60) == [0]
    hb.beat(step=2)  # atomic overwrite refreshes liveness
    assert hb.stale_hosts(1, timeout_s=60) == []
    with open(path) as f:
        assert json.load(f)["step"] == 2


def test_straggler_monitor_warmup_and_median():
    m = StragglerMonitor(factor=2.0, window=10, warmup=3)
    assert m.median() is None
    assert not m.observe(0, 10.0)  # warmup: even a huge step is not flagged
    assert not m.observe(1, 0.1)
    assert not m.observe(2, 0.1)
    m.observe(3, 0.1)
    assert m.median() == pytest.approx(0.1)
    assert not m.flagged


def test_run_restartable_keyboard_interrupt_passes_through(tmp_path):
    calls = []

    def step_fn(state, batch):
        calls.append(1)
        raise KeyboardInterrupt

    def batches():
        while True:
            yield None

    with pytest.raises(KeyboardInterrupt):
        run_restartable(step_fn, lambda: {"n": jnp.int32(0)}, batches(),
                        ckpt_dir=str(tmp_path), total_steps=5,
                        max_restarts=3)
    assert len(calls) == 1  # ctrl-C must not be treated as a crash


def test_run_restartable_resumes_from_committed_step(tmp_path):
    """A crash after step 7 resumes from the last committed multiple of
    save_every (5), replaying 6-7 — the crash-only contract."""
    crashed = {"done": False}
    seen = []

    def init_state():
        return {"n": jnp.int32(0)}

    def step_fn(state, batch):
        n = int(state["n"])
        if n + 1 == 8 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("boom")
        return {"n": state["n"] + 1}

    def batches():
        while True:
            yield None

    state, monitor = run_restartable(
        step_fn, init_state, batches(), ckpt_dir=str(tmp_path),
        total_steps=10, save_every=5, max_restarts=2,
        on_step=lambda s, st, dt: seen.append(s))
    assert int(state["n"]) == 10
    # first attempt reached 7, restart resumed at 6 (after committed 5)
    assert seen == [1, 2, 3, 4, 5, 6, 7, 6, 7, 8, 9, 10]
    assert ckpt.latest_step(str(tmp_path)) == 10


def test_run_restartable_saves_final_partial_interval(tmp_path):
    """total_steps not a multiple of save_every still commits the final
    state (the ``step == total_steps`` clause)."""
    state, _ = run_restartable(
        lambda s, b: {"n": s["n"] + 1}, lambda: {"n": jnp.int32(0)},
        iter(lambda: None, 1), ckpt_dir=str(tmp_path), total_steps=7,
        save_every=5)
    assert int(state["n"]) == 7
    assert ckpt.latest_step(str(tmp_path)) == 7


def test_heartbeat_startup_grace_for_never_beaten_hosts(tmp_path):
    """PR-7 regression: a freshly created monitor must not flag peers
    that simply have not beaten yet (their files legitimately do not
    exist at pod start) — only after the startup grace lapses."""
    hb = Heartbeat(str(tmp_path), host_id=0, interval_s=10.0)
    hb.beat(step=1)
    assert hb.stale_hosts(3, timeout_s=60) == []  # within 3x interval grace
    hb._created -= hb.startup_grace_s + 1.0       # grace lapses
    assert hb.stale_hosts(3, timeout_s=60) == [1, 2]


def test_heartbeat_grace_does_not_cover_corrupt_files(tmp_path):
    """The grace window is for *absent* beats; a host that wrote garbage
    did beat — and is stale immediately, grace or not."""
    hb = Heartbeat(str(tmp_path), host_id=0)
    hb.beat(step=1)
    with open(os.path.join(str(tmp_path), "host_1.hb"), "w") as f:
        f.write("{not json")
    assert hb.stale_hosts(2, timeout_s=60) == [1]


def test_heartbeat_grace_window_configurable(tmp_path):
    hb = Heartbeat(str(tmp_path), host_id=0, startup_grace_s=0.0)
    hb._created -= 1.0
    assert hb.stale_hosts(2, timeout_s=60) == [0, 1]


def test_run_restartable_fast_forwards_reiterable_batches(tmp_path):
    """PR-7 regression: restoring step N from a re-iterable source must
    feed batch N to step N+1 — the old ``iter(batches)`` replayed batch
    0 against the restored step."""
    crashed = {"done": False}
    pairs = []  # (step-entering, batch consumed)

    def step_fn(state, batch):
        n = int(state["n"])
        pairs.append((n, batch))
        if n + 1 == 8 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("boom")
        return {"n": state["n"] + 1}

    batches = list(range(100))  # re-iterable: restart must fast-forward
    state, _ = run_restartable(
        step_fn, lambda: {"n": jnp.int32(0)}, batches,
        ckpt_dir=str(tmp_path), total_steps=10, save_every=5,
        max_restarts=2)
    assert int(state["n"]) == 10
    # every step (first run and resumed replay alike) consumed ITS batch
    assert all(b == n for n, b in pairs)
    assert [n for n, _ in pairs] == [0, 1, 2, 3, 4, 5, 6, 7, 5, 6, 7, 8, 9]


def test_run_restartable_seekable_batches(tmp_path):
    """A source with ``seek(step)`` is positioned directly (no
    fast-forward consumption)."""

    class Seekable:
        def __init__(self, n):
            self.n = n
            self.pos = 0
            self.seeks = []

        def seek(self, step):
            self.seeks.append(step)
            self.pos = step

        def __iter__(self):
            while self.pos < self.n:
                v = self.pos
                self.pos += 1
                yield v

    crashed = {"done": False}
    pairs = []

    def step_fn(state, batch):
        n = int(state["n"])
        pairs.append((n, batch))
        if n + 1 == 7 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("boom")
        return {"n": state["n"] + 1}

    src = Seekable(100)
    state, _ = run_restartable(
        step_fn, lambda: {"n": jnp.int32(0)}, src,
        ckpt_dir=str(tmp_path), total_steps=8, save_every=5, max_restarts=2)
    assert int(state["n"]) == 8
    assert src.seeks == [0, 5]  # fresh start, then restored step
    assert all(b == n for n, b in pairs)


def test_run_restartable_fast_forward_exhaustion_is_an_error(tmp_path):
    """Restoring past the end of a short re-iterable source must say so
    instead of silently feeding batch 0."""
    ckpt.save(str(tmp_path), 5, {"n": jnp.int32(5)})
    with pytest.raises(ValueError, match="fast-forwarding"):
        run_restartable(
            lambda s, b: {"n": s["n"] + 1}, lambda: {"n": jnp.int32(0)},
            [0, 1, 2], ckpt_dir=str(tmp_path), total_steps=10,
            save_every=5, max_restarts=0)
