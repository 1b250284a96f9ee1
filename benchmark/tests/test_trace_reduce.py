"""The reduction from a profiler trace to device time, busy time, idle
share and idle gaps, on traces small enough to keep in the repository."""

import json
import statistics
from pathlib import Path

import pytest

from benchmark import trace_reduce as T

DATA = Path(__file__).parent / "data"


def test_union_merges_overlaps_and_keeps_gaps():
    assert T.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]


def test_reduce_on_a_synthetic_trace():
    events = {
        "devices": {
            "/device:TPU:0": {
                "modules": [
                    ["jit__stage_hash(11)", 10, 20],
                    ["jit__stage_final(12)", 40, 10],
                    ["jit__stage_hash(11)", 70, 20],
                    ["jit__stage_hash(11)", 95, 20],  # cut by the window's end
                    ["jit__stage_hash(11)", 120, 20],  # after the window
                ],
                "ops": [],
            },
        },
        "host": [
            ["bench.window", 0, 100],
            ["bench.build", 50, 15],
        ],
    }
    s = T.reduce(events, (0, 100), host_spans=[["bls_marshal", 28, 40]])
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(55e-9)
    assert s.idle_pct == pytest.approx(45.0)
    assert s.programs == {
        "jit__stage_hash": [pytest.approx(40e-9), 2],
        "jit__stage_final": [pytest.approx(10e-9), 1],
    }
    assert s.ms_per_launch("_stage_hash") == pytest.approx(20e-6)
    assert s.launches["jit__stage_hash"] == [
        [pytest.approx(10e-6), pytest.approx(20e-6)],
        [pytest.approx(70e-6), pytest.approx(20e-6)],
    ]
    assert s.ms_per_launch("_stage_group") is None
    # gaps: [0,10) window only, [30,40) marshal, [50,70) build, [90,95) window
    assert dict(s.idle_by_host) == {
        "bench.window": pytest.approx(15e-9),
        "bls_marshal": pytest.approx(10e-9),
        "bench.build": pytest.approx(20e-9),
    }


def test_a_trace_recorded_on_the_cpu():
    jax = pytest.importorskip("jax")
    pd = jax.profiler.ProfileData.from_file(str(DATA / "cpu_trace.xplane.pb"))
    events = T.events_from_profile(pd, cpu=True)
    assert list(events["devices"]) == ["/host:CPU"]
    window = T.window_of(events)
    s = T.reduce(events, window)
    assert s.window_s == pytest.approx((window[1] - window[0]) / 1e9)
    # three 30 ms rounds of host work with the device idle
    assert 0 < s.busy_s < 0.03
    assert 70 < s.idle_pct < 100
    label, seconds = s.idle_by_host[0]
    assert label == "bench.build" and seconds > 0.08


def test_a_trace_is_cut_where_the_device_dropped_its_buffers():
    events = {
        "devices": {"/device:TPU:0": {"modules": [["jit_f(1)", 0, 50]], "ops": []}},
        "host": [],
        "dropped_ns": 60,
    }
    s = T.reduce(events, (0, 100))
    assert s.window_s == pytest.approx(60e-9)
    assert s.busy_s == pytest.approx(50e-9)


def test_an_excerpt_of_the_first_chip_trace():
    events = json.loads((DATA / "chip_block_excerpt.json").read_text())
    modules = events["devices"]["/device:TPU:0"]["modules"]
    lo, hi = events["window"]
    s = T.reduce(events, (lo, hi))
    prep = [
        d for n, st, d in modules
        if n.startswith("jit__stage_prep(") and lo <= st and st + d <= hi
    ]
    assert s.programs["jit__stage_prep"] == [pytest.approx(sum(prep) / 1e9), len(prep)]
    assert s.ms_per_launch("_stage_prep") == pytest.approx(statistics.median(prep) / 1e6)
    # the staged programs run back to back; the device waits while the
    # host marshals the next block inside the harness's submit call
    busy = sum(min(st + d, hi) - st for _, st, d in modules if lo <= st < hi)
    assert s.busy_s == pytest.approx(busy / 1e9, rel=1e-6)
    assert 10 < s.idle_pct < 25
    assert s.idle_by_host[0][0] == "bench.submit"
    assert [name for name, _ in s.top_programs(2)] == [
        "jit__stage_prep", "jit__stage_hash",
    ]


def test_a_trace_without_a_device_is_refused():
    jax = pytest.importorskip("jax")
    pd = jax.profiler.ProfileData.from_file(str(DATA / "cpu_trace.xplane.pb"))
    # read as a chip trace, the host's operations do not stand for a TPU
    with pytest.raises(T.TraceError):
        T.events_from_profile(pd)
    empty = {"devices": {}, "host": [["bench.trace", 0, 100]]}
    with pytest.raises(T.TraceError):
        T.reduce(empty, (0, 100))
    idle = {
        "devices": {"/device:TPU:0": {"modules": [["jit_f(1)", 200, 5]], "ops": []}},
        "host": [],
    }
    with pytest.raises(T.TraceError):
        T.reduce(idle, (0, 100))


def test_a_stray_short_launch_does_not_move_the_time_per_launch():
    launches = [["jit__stage_hash(11)", 100 * i, 100] for i in range(3)]
    launches.append(["jit__stage_hash(11)", 300, 1])
    events = {
        "devices": {"/device:TPU:0": {"modules": launches, "ops": []}},
        "host": [],
    }
    s = T.reduce(events, (0, 400))
    assert s.programs["jit__stage_hash"] == [pytest.approx(301e-9), 4]
    assert s.ms_per_launch("_stage_hash") == pytest.approx(100e-6)
    assert [d for _, d in s.launches["jit__stage_hash"]] == [
        pytest.approx(x) for x in (100e-6, 100e-6, 100e-6, 1e-6)
    ]
