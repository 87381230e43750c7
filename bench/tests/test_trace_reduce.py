"""The trace reduction on a small trace built here, in the shape that
``jax.profiler.ProfileData`` gives (planes, lines, events in ns)."""
from __future__ import annotations

import types

import pytest

from bench import trace_reduce


def ev(name, start_ms, dur_ms):
    return types.SimpleNamespace(name=name, start_ns=start_ms * 1e6,
                                 duration_ns=dur_ms * 1e6)


def line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


def trace(n_devices=1):
    host = plane("/host:CPU", [line("python3", [
        ev("bench.window", 10, 100),
        ev("bench.engine_step", 10, 30), ev("bench.call.decode", 12, 2),
        ev("bench.engine_step", 50, 20), ev("bench.wait", 80, 25),
        ev("other", 0, 200)])])
    devs = []
    for i in range(n_devices):
        devs.append(plane(f"/device:TPU:{i}", [
            line("XLA Modules", [ev("jit_decode_fn(7)", 15, 20),
                                 ev("jit_prefill_fn(8)", 55, 10),
                                 ev("jit_decode_fn(7)", 0, 5)]),
            line("XLA Ops", [ev("fusion.1", 15, 12), ev("fusion.2", 25, 10),
                             ev("dot.3", 55, 10), ev("fusion.1", 0, 5)])]))
    return [host, plane("/device:TPU:0 SparseCore", [])] + devs


def test_busy_window_modules():
    r = trace_reduce.reduce_planes(trace())
    assert r["window_s"] == pytest.approx(0.100)
    # ops 15-35 (two overlapping) and 55-65 inside 10-110; 0-5 is outside
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["modules"] == {"jit_decode_fn": [pytest.approx(0.020)],
                            "jit_prefill_fn": [pytest.approx(0.010)]}
    assert [s[0] for s in r["spans"]].count("bench.engine_step") == 2
    ops = dict(r["device_ops"])
    assert ops["jit_decode_fn/fusion.1"] == pytest.approx(0.012)
    assert ops["jit_prefill_fn/dot.3"] == pytest.approx(0.010)


def test_idle_gaps_named_by_host_span():
    r = trace_reduce.reduce_planes(trace())
    gaps = {name: round(s, 6) for name, s in r["idle_gaps"]}
    # 65-110 (wait spans 80-105, midpoint 87.5), 35-55 (midpoint 45: no
    # step span), 10-15 (midpoint 12.5: the decode call inside a step)
    assert gaps == {"bench.wait": 0.045, "no span": 0.020,
                    "bench.call.decode": 0.005}


def test_overlap_and_mean_over_devices():
    r = trace_reduce.reduce_planes(trace(n_devices=2))
    assert r["busy_s"] == pytest.approx(0.030)
    assert trace_reduce.overlap(r["busy"], 0.010, 0.040) == pytest.approx(0.020)


def test_no_window_or_device_reads_nothing():
    assert trace_reduce.reduce_planes(trace()[:1]) is None
    assert trace_reduce.reduce_planes(trace()[1:]) is None
