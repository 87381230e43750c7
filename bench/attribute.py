#!/usr/bin/env python3
"""Attribute a traced window of a serving cell to the program's own
instruments: its ``serve.*`` host spans, the named scopes of its two step
programs, its request times and its engine counters.

  python3 bench/attribute.py --workload qwen2-1.5b.chat --seed <n> \\
      [--seconds 51] [--out <dir>]

On the chip, in one process: set-up, warm-up and a traced window as
``bench/run.py --trace 1`` makes them (``bench.serving``), the trace and
the two programs' HLO text kept under ``--out`` (default
``bench_out/attribute``).  Prints one JSON line: ``metrics`` (the readers
below), the engine's ``counters`` at the window's open and close,
``scopes``, ``idle_gaps`` and ``idle_by_span`` (:func:`reduce_serve`),
the window's busy and total seconds, each program's runs and device
seconds (what its scopes sum to at most), and each request's ``due``,
``slot`` and ``admit`` times.  No correctness check runs; ``bench/run.py``
is the benchmark.

The benchmark's own record (``bench.serving.Loop._record``) and reduction
(``bench.trace_reduce``) do not carry these yet; the readers take the
record with three more keys, as this tool builds it:

* ``requests[i]["slot"]``: when the request was given a slot, seconds from
  the window's start (``RequestMetrics.slot_time``);
* ``counters``: ``{"open": {...}, "close": {...}}``, the engine's monotone
  counters at the window's open and close;
* ``serve``: :func:`reduce_serve` of the trace.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import sys

if __name__ == "__main__":
    import pathlib
    import time
    T_START = time.time()
    ROOT = pathlib.Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import trace_reduce  # noqa: E402

# the programs' named scopes (models/paged_lm.py), innermost wins
SCOPES = ("qkv", "kv_write", "kv_read", "attention", "o_proj", "mlp",
          "lm_head", "sample")
UNSCOPED = "(unscoped)"
COUNTERS = ("steps", "decode_steps", "prefill_chunks", "kv_pages_live",
            "kv_pages_read", "prefill_backlog_chunks")
_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?'
                      r'metadata=\{[^}]*op_name="([^"]*)"')
_PATH = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r'%?([\w.\-]+)')
_RUN_ID = re.compile(r"\(\d+\)$")


def scope_of(path: str) -> str:
    """The innermost named scope on an op's metadata path."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def hlo_paths(text: str) -> dict:
    """``{instruction name: op_name}`` of a compiled program's HLO text,
    for op events whose stats carry no path.  Names are unique only inside
    one program: keep one map per program."""
    out = {}
    for ln in text.splitlines():
        m = _OP_NAME.match(ln)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def hlo_module(text: str) -> str:
    """The program's name in its HLO text (``jit_decode_fn``)."""
    return re.match(r"HloModule ([^\s,]+)", text).group(1)


def _path(ev, hlo: dict) -> str:
    """An op event's metadata path: its ``tf_op`` stat, an ``op_name`` in
    its name or stats (HLO text), else the program's HLO by the op's
    instruction name (an event may be named by its HLO text)."""
    stats = dict(getattr(ev, "stats", None) or ())
    tf_op = stats.get("tf_op")
    if isinstance(tf_op, str) and "/" in tf_op:
        return tf_op
    for text in [ev.name, *stats.values()]:
        m = isinstance(text, str) and _PATH.search(text)
        if m:
            return m.group(1)
    return hlo.get(_INSTR.match(ev.name).group(1), "")


def _self_times(events):
    """[(event, seconds)] of one device line, each interval counted once:
    an op that encloses others (a ``while`` around its body) keeps only the
    time none of them covers."""
    evs = sorted(((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                   ev) for ev in events), key=lambda x: (x[0], -x[1]))
    own = []
    stack = []                      # events still open: (end, index)
    for i, (s, e, ev) in enumerate(evs):
        while stack and stack[-1][0] <= s:
            stack.pop()
        own.append(e - s)
        if stack:
            pe, parent = stack[-1]
            own[parent] -= min(e, pe) - s
        stack.append((e, i))
    return [(evs[i][2], t) for i, t in enumerate(own) if t > 0.0]


def _innermost(spans, t) -> str:
    inner = [x for x in spans if x[1] <= t <= x[2]]
    return min(inner, key=lambda x: x[2] - x[1])[0] if inner else "no span"


def idle_gaps(busy, spans, lo, hi, top=10) -> list:
    """The ``top`` longest idle gaps inside [lo, hi], outside the merged
    ``busy`` intervals, each ``[name, seconds]`` named by the innermost of
    ``spans`` covering its midpoint."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), key=lambda g: g[0] - g[1])
    return [[_innermost(spans, (s + e) / 2), e - s] for s, e in gaps[:top]]


def idle_by_span(busy, spans, lo, hi) -> dict:
    """All idle seconds inside [lo, hi], each put down to the innermost of
    ``spans`` covering it."""
    pts = sorted({lo, hi, *(t for _, s, e in spans for t in (s, e)
                            if lo < t < hi)})
    out = collections.Counter()
    for a, b in zip(pts, pts[1:]):
        idle = (b - a) - trace_reduce.overlap(busy, a, b)
        if idle > 0.0:
            out[_innermost(spans, (a + b) / 2)] += idle
    return dict(out)


def reduce_serve(planes, hlo: dict | None = None) -> dict | None:
    """What the program's instruments add to ``trace_reduce.reduce_planes``
    of the same planes: ``spans`` (host spans named ``serve.*`` or
    ``bench.*`` inside the window, ``(name, start, end)`` in seconds),
    ``idle_gaps`` (the ten longest on device 0) and ``idle_by_span`` (all of
    its idle time), named by the innermost of those spans, and ``scopes``
    (``{program: {scope: device seconds}}`` on device 0, ops whose path
    names no scope under ``(unscoped)``), over the program runs that
    ``reduce_planes``' ``modules`` counts, those that start inside the
    window, so a program's scopes sum to no more than its module seconds.
    ``hlo``: ``{program: hlo_paths(...)}`` for ops whose events carry no
    path."""
    planes = list(planes)
    base = trace_reduce.reduce_planes(planes)
    if base is None:
        return None
    hlo = hlo or {}
    spans, dev0 = [], None
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("serve.", "bench.")):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            n = int(plane.name.rsplit(":", 1)[1])
            lines = {ln.name: ln for ln in plane.lines}
            if trace_reduce.OPS in lines and (dev0 is None or n < dev0[0]):
                dev0 = (n, lines)
    lo, hi = next((s, e) for n, s, e in spans if n == "bench.window")
    lines = dev0[1]
    mods = sorted((ev.start_ns * 1e-9, _RUN_ID.sub("", ev.name))
                  for ev in (lines[trace_reduce.MODULES].events
                             if trace_reduce.MODULES in lines else ()))
    starts = [t for t, _ in mods]
    scopes = collections.defaultdict(collections.Counter)
    for ev, t in _self_times(lines[trace_reduce.OPS].events):
        i = bisect.bisect_right(starts, ev.start_ns * 1e-9) - 1
        if i < 0 or not lo <= starts[i] < hi:
            continue
        prog = mods[i][1]
        scopes[prog][scope_of(_path(ev, hlo.get(prog, {})))] += t
    inside = [x for x in spans
              if x[2] > lo and x[1] < hi and x[0] != "bench.window"]
    return dict(spans=inside,
                idle_gaps=idle_gaps(base["busy"], inside, lo, hi),
                idle_by_span=idle_by_span(base["busy"], inside, lo, hi),
                scopes={p: dict(c) for p, c in scopes.items()})


# -- readers: the record as this tool builds it -> number | None ------------

def _mean(xs):
    return sum(xs) / len(xs) if xs else None


def _in_window(t, rec):
    return t is not None and 0.0 <= t < rec["window_s"]


def slot_wait_ms_mean(rec):
    """Due time to slot, over requests given a slot inside the window."""
    m = _mean([r["slot"] - r["due"] for r in rec["requests"]
               if _in_window(r.get("slot"), rec)])
    return None if m is None else m * 1e3


def prefill_wait_ms_mean(rec):
    """Slot to first prefill chunk, over requests whose first chunk ran
    inside the window."""
    m = _mean([r["admit"] - r["slot"] for r in rec["requests"]
               if r.get("slot") is not None and _in_window(r["admit"], rec)])
    return None if m is None else m * 1e3


def _delta(rec, key):
    c = rec.get("counters")
    if not c:
        return None
    return c["close"][key] - c["open"][key]


def prefill_backlog_chunks(rec):
    """Mean over the window's engine steps of the prefill chunks owed."""
    n = _delta(rec, "steps")
    return _delta(rec, "prefill_backlog_chunks") / n if n else None


def kv_live_share(rec):
    """Live KV pages over the pages the decode steps read, %."""
    read = _delta(rec, "kv_pages_read")
    return 100.0 * _delta(rec, "kv_pages_live") / read if read else None


def step_host_ms(rec):
    """Mean over ``serve.step`` spans of the span minus device busy time
    inside it: the program's own twin of ``engine_host_ms``."""
    tr, sv = rec["trace"], rec.get("serve")
    if not tr or not sv:
        return None
    xs = [(e - s) - trace_reduce.overlap(tr["busy"], s, e)
          for n, s, e in sv["spans"] if n == "serve.step"]
    return 1e3 * sum(xs) / len(xs) if xs else None


def decode_kv_ms(rec):
    """Device ms per decode step under the ``kv_read`` and ``kv_write``
    scopes of the decode program."""
    sv = rec.get("serve")
    runs = len((rec["trace"] or {}).get("modules", {}).get("jit_decode_fn",
                                                          ()))
    if not sv or not runs:
        return None
    sc = sv["scopes"].get("jit_decode_fn", {})
    return 1e3 * (sc.get("kv_read", 0.0) + sc.get("kv_write", 0.0)) / runs


READERS = {"slot_wait_ms_mean": slot_wait_ms_mean,
           "prefill_wait_ms_mean": prefill_wait_ms_mean,
           "prefill_backlog_chunks.chat": prefill_backlog_chunks,
           "step_host_ms.chat": step_host_ms,
           "kv_live_share.chat": kv_live_share,
           "decode_kv_ms.chat": decode_kv_ms}


# -- the chip run -----------------------------------------------------------

def _counters(eng) -> dict:
    return {k: getattr(eng.metrics, k) for k in COUNTERS}


def _hlo(eng, steps, out: str) -> dict:
    """``{program: op paths}`` of the two compiled step programs ``steps``
    (the engine's jitted pair, from the persistent compilation cache the
    warm-up filled), as the trace names their ops; their HLO text is kept
    under ``out``."""
    import numpy as np
    s = eng.n_slots
    dec = steps.decode.lower(
        eng.params, np.zeros(s, np.int32), np.zeros(s, bool),
        np.zeros(s, np.float32), np.zeros((s, 2), np.uint32), eng.cache)
    pre = steps.prefill.lower(
        eng.params, np.zeros(eng.prefill_chunk, np.int32), np.int32(1),
        np.int32(0), np.float32(0), np.zeros(2, np.uint32), eng.cache)
    paths = {}
    for low in (dec, pre):
        text = low.compile().as_text()
        name = hlo_module(text)
        with open(os.path.join(out, f"{name}.hlo.txt"), "w") as f:
            f.write(text)
        paths[name] = hlo_paths(text)
    return paths


def _window(ctx, out: str):
    """Run the cell's window; the tracer copies its trace to ``out``
    (``BENCH_KEEP_TRACE``, set for this window only)."""
    import importlib
    keep = os.environ.get("BENCH_KEEP_TRACE")
    os.environ["BENCH_KEEP_TRACE"] = out
    try:
        arrivals = importlib.import_module(
            f"bench.arrivals.{ctx.mix['arrivals']}")
        return arrivals.run(ctx)
    finally:
        if keep is None:
            del os.environ["BENCH_KEEP_TRACE"]
        else:
            os.environ["BENCH_KEEP_TRACE"] = keep


def attribute(cell, device: dict, seed: int, seconds: float, out: str, *,
              t_start: float) -> dict:
    """One traced window of ``cell`` (a ``harness.Cell``) with the trace
    kept under ``out``; returns the line :func:`main` prints."""
    import time

    from jax.profiler import ProfileData

    from bench import harness
    snaps, holder = [], {}

    def hook(eng):
        # the context's engine hook is the one seam into the window: count
        # before each step, and keep the jitted pair the loop later wraps
        step = eng.step

        def counted():
            snaps.append((time.monotonic(), _counters(eng)))
            return step()
        eng.step = counted
        holder.update(eng=eng, steps=eng.steps)

    ctx = harness.Context(cfg=cell.cfg, mix=cell.mix, seed=seed,
                          seconds=seconds, trace=True, t_start=t_start,
                          compiles=harness.CompileCount(), hook=hook)
    loop = _window(ctx, out)
    eng = holder.pop("eng")
    rec = loop.record
    w0 = loop.w0
    for r, req in zip(rec["requests"], loop.requests):
        t = req.metrics.slot_time
        r["slot"] = None if t is None else t - w0
    rec["counters"] = {"open": next(c for t, c in snaps if t >= w0),
                       "close": _counters(eng)}
    hlo = _hlo(eng, holder.pop("steps"), out)
    del eng
    loop.release()
    files = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    planes = ProfileData.from_file(max(files, key=os.path.getmtime)).planes
    rec["serve"] = reduce_serve(planes, hlo)
    tr, sv = rec["trace"] or {}, rec["serve"] or {}
    return {"workload": cell.name, "seed": seed, "device": device,
            "metrics": {k: f(rec) for k, f in READERS.items()},
            "counters": rec["counters"], "scopes": sv.get("scopes"),
            "idle_gaps": sv.get("idle_gaps"),
            "idle_by_span": sv.get("idle_by_span"),
            "busy_s": tr.get("busy_s"), "window_s": tr.get("window_s"),
            "module_s": {k: [len(v), sum(v)]
                         for k, v in tr.get("modules", {}).items()},
            "requests": [[r["due"], r["slot"], r["admit"]]
                         for r in rec["requests"]]}


def main(argv=None) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default="bench_out/attribute")
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.resolve(args.workload)
    device = harness.check_device(cell.chips)
    harness.enable_cache()
    os.makedirs(args.out, exist_ok=True)
    line = attribute(cell, device, args.seed, args.seconds, args.out,
                     t_start=T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
