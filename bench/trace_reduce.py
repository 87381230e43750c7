"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the metric
readers use.

Read with ``jax.profiler.ProfileData``, nothing else.  Device planes are
those named ``/device:TPU:<n>``; on each, the line ``XLA Ops`` holds one
event per operation run and ``XLA Modules`` one per program run.  Host
spans are the benchmark's own ``bench.*`` annotations on the host plane.
Host and device events share the profiler's clock.

Everything is clipped to the ``bench.window`` span:

* ``busy_s``: length of the union of the operation intervals, mean over
  the devices; ``window_s``: the span's length;
* ``modules``: device seconds of each program run, keyed by the program's
  name without its ``(id)`` suffix;
* ``busy``: device 0's merged busy intervals (for host self time);
* ``spans``: the host spans, ``(name, start, end)`` in seconds;
* ``device_ops``: the ten operations with most device time, summed over
  devices, keyed ``program/op``;
* ``idle_gaps``: the ten longest idle gaps on device 0, each named by the
  innermost host span that covers its midpoint.
"""
from __future__ import annotations

import collections
import glob
import os
import re

OPS, MODULES = "XLA Ops", "XLA Modules"
_ID = re.compile(r"\(\d+\)$")


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def overlap(intervals, lo, hi) -> float:
    """Length of merged ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in _clip(intervals, lo, hi))


def reduce_planes(planes) -> dict:
    """``planes``: iterable of objects with ``name`` and ``lines``, whose
    lines have ``name`` and ``events`` (``name``, ``start_ns``,
    ``duration_ns``), as ``ProfileData`` gives them."""
    spans, devices = [], []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS in lines:
                devices.append((plane.name, lines))
    win = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not win or not devices:
        return None
    lo, hi = win[0]
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    busy_s, modules, ops = [], collections.defaultdict(list), \
        collections.Counter()
    busy0 = None
    for name, lines in devices:
        ivs, mods = [], []
        if MODULES in lines:
            for ev in lines[MODULES].events:
                s = ev.start_ns * 1e-9
                mods.append((s, _ID.sub("", ev.name)))
                if lo <= s < hi and name == devices[0][0]:
                    modules[_ID.sub("", ev.name)].append(ev.duration_ns * 1e-9)
        mods.sort()
        for ev in lines[OPS].events:
            s = ev.start_ns * 1e-9
            e = s + ev.duration_ns * 1e-9
            if e <= lo or s >= hi:
                continue
            ivs.append((s, e))
            ops[f"{_program_at(mods, s)}/{ev.name}"] += min(e, hi) - max(s, lo)
        merged = _merge(_clip(ivs, lo, hi))
        busy_s.append(sum(e - s for s, e in merged))
        if busy0 is None:
            busy0 = merged
    return dict(window_s=hi - lo, busy_s=sum(busy_s) / len(busy_s),
                modules=dict(modules), busy=busy0,
                spans=[x for x in spans if x[2] > lo and x[1] < hi],
                device_ops=[[k, v] for k, v in ops.most_common(10)],
                idle_gaps=_idle_gaps(busy0, lo, hi, spans))


def _program_at(mods, t) -> str:
    """Name of the last program that started at or before ``t``."""
    i = _bisect(mods, t)
    return mods[i][1] if i >= 0 else "?"


def _bisect(mods, t) -> int:
    lo, hi = 0, len(mods)
    while lo < hi:
        mid = (lo + hi) // 2
        if mods[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    return lo - 1


def _idle_gaps(busy, lo, hi, spans, top=10):
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        inner = [x for x in spans if x[1] <= mid <= x[2]
                 and x[0] != "bench.window"]
        name = min(inner, key=lambda x: x[2] - x[1])[0] if inner else "no span"
        out.append([name, e - s])
    return out


def reduce_dir(path: str) -> dict | None:
    """Reduce the newest ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        return None
    return reduce_planes(ProfileData.from_file(max(files, key=os.path.getmtime)).planes)
