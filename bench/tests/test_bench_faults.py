"""The comparison that decides ``correct``, driven through the rest of a
run on the CPU at a small size: sound runs pass, the bfloat16 control
fails, and each fault a cell can have, planted under the timed path,
makes ``correct`` false.  (A cell on one chip has no exchange between
chips, and one caller has no batch to leave half of out.)"""
import jax
import numpy as np
import pytest

from bench import harness, manifest

MAN = manifest.load()
SMALL = (16, 24, 40)
CELLS = [w["name"] for w in MAN["workloads"]]


def _run(name, seed=2 ** 31 + 9, control=False):
    return harness.run_cell(manifest.cell(MAN, name), seed, 0.4, shape=SMALL,
                            devices=jax.devices()[:1], with_control=control)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_control_is_not(name):
    rec = _run(name, control=True)
    assert rec.correct, rec.checks
    assert rec.plan_builds_window == 0
    assert any(v > lim for v, lim in rec.control_checks.values()), \
        rec.control_checks


def _wrap_run(monkeypatch, alter):
    from repro.pipe.graph import Pipe

    orig = Pipe.run
    monkeypatch.setattr(Pipe, "run", lambda self, *a, **k: alter(
        self, orig(self, *a, **k)))


def test_answer_altered_where_produced_fails(monkeypatch):
    def alter(_, st):
        return type(st)(st.count, st.mean, st.m2 * 1.01, st.m3, st.m4,
                        st.order)
    _wrap_run(monkeypatch, alter)
    assert not _run("ct-same-variance").correct


def test_stale_answer_fails(monkeypatch):
    """Every call answers with the first input's state."""
    from repro.pipe.graph import Pipe

    orig, first = Pipe.run, {}

    def run(self, *a, **k):
        if "x" not in first:
            first["x"] = self.x
        return orig(Pipe(first["x"], self.batched, self.ops), *a, **k)
    monkeypatch.setattr(Pipe, "run", run)
    assert not _run("ct-same-variance").correct


def test_curvature_voxel_altered_fails(monkeypatch):
    from repro.core import filters

    orig = filters.gaussian_curvature

    def altered(x, **k):
        y = orig(x, **k)
        # one voxel off by a hundredth of the volume's largest value
        return y.at[3, 4, 5].add(0.01 * jax.numpy.max(jax.numpy.abs(y)))
    monkeypatch.setattr(filters, "gaussian_curvature", altered)
    assert not _run("ct-curvature").correct


def test_moment_errors_measure_each_leaf():
    moment_errors = manifest.module("results", "moment_state").moment_errors

    ref = (np.array([8.0]), np.array([1.0]), np.array([4.0]))
    e = moment_errors((np.array([8.0]), np.array([1.2]), np.array([5.0])),
                      ref)
    assert e == {"count_err": 0.0, "mean_err": pytest.approx(0.1),
                 "var_err": pytest.approx(0.25)}
