"""The trace reduction: busy and idle time, collective time and the
breakdown, on a hand-made trace with known answers and on traces
recorded on the chip."""
from pathlib import Path

import pytest

from bench import trace_reduce
from bench.trace_reduce import Trace

DATA = Path(__file__).parent / "data"
RECORDED = sorted(DATA.glob("trace_*.json.gz"))


def _hand_made():
    ops0 = [(0.0, 1.0, "fusion.1"), (0.5, 1.5, "fusion.2"),
            (2.0, 2.5, "all-reduce.1"), (3.0, 3.2, "collective-permute-done"),
            (3.9, 4.5, "fusion.1")]
    ops1 = [(0.0, 2.0, "fusion.1"), (2.0, 3.0, "all-gather.3")]
    spans = [(0.0, 4.0, "bench/window"), (0.0, 1.6, "bench/call"),
             (1.6, 3.5, "bench/fetch"), (3.5, 4.0, "bench/call")]
    return Trace({0: ops0, 1: ops1}, spans)


def test_busy_is_the_union_inside_the_window():
    r = trace_reduce.reduce(_hand_made())
    assert r["window_s"] == 4.0
    # device 0: [0, 1.5] + [2, 2.5] + [3, 3.2] + [3.9, 4.0] (clipped)
    assert r["busy_s"][0] == pytest.approx(2.3)
    assert r["busy_s"][1] == pytest.approx(3.0)


def test_collective_time_counts_collectives_only():
    r = trace_reduce.reduce(_hand_made())
    assert r["collective_s"][0] == pytest.approx(0.7)
    assert r["collective_s"][1] == pytest.approx(1.0)


def test_device_ops_are_per_device_means_most_first():
    r = trace_reduce.reduce(_hand_made())
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "fusion.1"
    # fusion.1: 1.0 + 0.1 on device 0, 2.0 on device 1, over 2 devices
    assert r["device_ops"][0][1] == pytest.approx(1.55)
    assert [t for _, t in r["device_ops"]] == sorted(
        (t for _, t in r["device_ops"]), reverse=True)


def test_idle_gaps_are_named_by_the_host_span():
    r = trace_reduce.reduce(_hand_made())
    gaps = r["idle_gaps"]
    # device 0 idles in (1.5, 2.0), (2.5, 3.0), (3.2, 3.9); device 1 in
    # (3.0, 4.0)
    assert [round(t, 6) for _, t in gaps] == [1.0, 0.7, 0.5, 0.5]
    # (3.0, 4.0): call and fetch overlap it 0.5 each; the shorter wins
    assert gaps[0][0] == "call"
    # (3.2, 3.9): fetch overlaps it 0.3, call 0.4
    assert gaps[1][0] == "call"
    # (1.5, 2.0) and (2.5, 3.0): fetch
    assert [n for n, _ in gaps[2:]] == ["fetch", "fetch"]


def test_a_given_window_overrides_the_span():
    r = trace_reduce.reduce(_hand_made(), window=(0.0, 1.0))
    assert r["busy_s"] == [pytest.approx(1.0), pytest.approx(1.0)]
    assert r["idle_gaps"] == []


def test_no_window_or_no_device_raises():
    with pytest.raises(ValueError):
        trace_reduce.reduce(Trace({0: []}, []))
    with pytest.raises(ValueError):
        trace_reduce.reduce(Trace({}, [(0.0, 1.0, "bench/window")]))


def test_union_merges_overlaps():
    assert trace_reduce.union([(2, 3), (0, 1), (0.5, 2.5)]) == [(0, 3)]


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace_reduces_soundly(path):
    assert path.stat().st_size < 1 << 20
    t = Trace.from_json(str(path))
    r = trace_reduce.reduce(t)
    w = r["window_s"]
    assert w > 0
    for busy, coll in zip(r["busy_s"], r["collective_s"]):
        assert 0 < busy <= w and 0 <= coll <= busy
    assert 0 < len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert all(n and n != "none" for n, _ in r["idle_gaps"])
    # the busy union is at most the sum of the operations' own times
    total = sum(t for _, t in r["device_ops"])
    assert sum(r["busy_s"]) / len(r["busy_s"]) <= total + 1e-9 or \
        len(r["device_ops"]) == 10
