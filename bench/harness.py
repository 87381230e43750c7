"""Resolve a cell of ``BENCHMARK.json`` to its files and run it once.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file found by its name, so a cell or metric is added by adding files:

  bench/configs/<config>.json   published config.json keys + serve geometry,
                                and the paths of two modules: ``arch``
                                (``bench/archs/dense_decoder.py`` says what
                                it provides) and ``reference`` (``BLOCK``
                                and ``logit_stats``, ``bench.check``)
  bench/traffic/<traffic>.json  the mix's parameters (``bench.loadgen``)
  bench/limits/<workload>.json  the limits of the correctness check
  bench/metrics/<metric>.py     ``read(record) -> number | None``

:func:`run` is the one measured entry point.  The tools that calibrate the
benchmark (``bench/calibrate.py``, ``bench/sweep.py``) and the tests call
its parts: :func:`resolve`, :func:`window` and :func:`result`.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import importlib.util
import json
import os
import pathlib
import re
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent
CACHE = REPO / ".jax_cache"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    bench: dict               # BENCHMARK.json
    cfg: dict
    mix: dict
    limits: dict


@dataclasses.dataclass
class Context:
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    compiles: object = None      # CompileCount
    hook: object = None          # tests only: called with the engine
    min_tokens: int = 0          # greedy tokens served that the check needs


@dataclasses.dataclass
class Outcome:
    """What a window left, once the program's state is freed."""
    record: dict              # bench.serving.Loop.record
    served: list              # [(prompt, served tokens)] of greedy requests
    memory_peak_bytes: int


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def find(items, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"no {what} named {name!r}")


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


MODULE_KEYS = ("arch", "reference")


def load_config(path: pathlib.Path) -> dict:
    """A configuration file; it has to name its two modules."""
    cfg = load_json(path)
    missing = [k for k in MODULE_KEYS if k not in cfg]
    if missing:
        raise SystemExit(f"configuration {path} names no "
                         f"{' and no '.join(map(repr, missing))} module")
    return cfg


@functools.cache
def module_at(path: str):
    """The module in the file at ``path``, from the repository's root
    unless absolute; loaded once a process, so that what it compiles is
    compiled once."""
    full = REPO / path
    name = "bench_module_" + re.sub(r"\W", "_", str(full))
    spec = importlib.util.spec_from_file_location(name, full)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch(cfg: dict):
    """The configuration's architecture module (``cfg["arch"]``)."""
    return module_at(cfg["arch"])


def reference(cfg: dict):
    """The configuration's plain reference (``cfg["reference"]``)."""
    return module_at(cfg["reference"])


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def resolve(workload: str, bench: dict | None = None,
            data: pathlib.Path = BENCH) -> Cell:
    bench = bench or load_json(REPO / "BENCHMARK.json")
    wl = find(bench["workloads"], workload, "workload")
    return Cell(name=workload, chips=wl["chips"], bench=bench,
                cfg=load_config(data / "configs" / f"{wl['config']}.json"),
                mix=load_json(data / "traffic" / f"{wl['traffic']}.json"),
                limits=load_json(data / "limits" / f"{workload}.json"))


def check_device(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero without ``chips`` TPU
    chips.  Nothing falls back to another platform."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", file=sys.stderr, flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX found platform "
                         f"{d.platform!r} ({d.device_kind}); the benchmark "
                         f"runs only on a TPU")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def enable_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, a
    fixed path inside the checkout, whatever the environment says; the
    program's launcher helper is handed the same directory."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.enable()}", file=sys.stderr)


def memory_peak(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


class CompileCount:
    """Backend compilations, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.n = 0

        def on(name, *_, **__):
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on)


def window(cell: Cell, seed: int, seconds: float, trace: bool, *,
           t_start: float, hook=None) -> Outcome:
    """Set-up, warm-up and the measured window; frees the program's state
    before it returns, so the reference runs on a free chip."""
    ctx = Context(cfg=cell.cfg, mix=cell.mix, seed=seed, seconds=seconds,
                  trace=trace, t_start=t_start, compiles=CompileCount(),
                  hook=hook, min_tokens=cell.limits["min_tokens"])
    arrivals = importlib.import_module(f"bench.arrivals.{cell.mix['arrivals']}")
    loop = arrivals.run(ctx)
    out = Outcome(record=loop.record, served=loop.served_greedy(),
                  memory_peak_bytes=memory_peak(cell.chips))
    loop.release()
    del loop
    gc.collect()
    return out


def result(cell: Cell, device: dict, out: Outcome, seed: int,
           trace: bool) -> dict:
    """The result line: the check against the reference, the metrics read
    from the record, and last the numbers compared beside their limits."""
    from bench import check
    rec = out.record
    rec.update(config=cell.cfg, device_kind=device["kind"])
    device = {**device, "memory_peak_bytes": out.memory_peak_bytes}
    if trace:
        tr = rec["trace"] or {}
        device["busy_s"] = tr.get("busy_s", 0.0)
        device["window_s"] = tr.get("window_s", rec["window_s"])

    w = rec["window_s"]
    # the window worked on every request due before its close that had not
    # ended before its start
    worked = [r for r in rec["requests"] if r["due"] < w
              and (r["finish"] is None or r["finish"] >= 0.0)]
    rejected = sum(1 for t in rec["rejected"] if 0.0 <= t < w)
    short = sum(1 for r in rec["requests"]
                if r["state"] == "finished" and r["n_out"] != r["max_new"])
    failed = rejected + sum(1 for r in worked
                            if r["state"] in ("failed", "cancelled"))
    t0 = time.monotonic()
    checks = check.run(cell.cfg, seed, out.served, short, cell.limits)
    ref_s = time.monotonic() - t0

    metrics = {}
    for m in cell_metrics(cell.bench, cell.name, trace):
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": check.passed(checks),
            "attempted": len(worked) + rejected,
            "failed": failed, "metrics": metrics, "device": device}
    if trace and rec["trace"]:
        line["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                             "idle_gaps": rec["trace"]["idle_gaps"]}
    line["load"] = load(rec)
    print(f"window: {w} s, {line['load']['due']} requests due, {len(worked)} "
          f"worked on, reference {ref_s:.1f} s; load "
          f"{json.dumps(line['load'])}", file=sys.stderr)
    for k, c in checks.items():
        lim = " ".join(f"{b} {c[b]}" for b in ("min", "max") if b in c)
        print(f"check {k}: {c['value']} ({lim})", file=sys.stderr)
    line["checks"] = checks
    return line


def load(rec: dict) -> dict:
    """How loaded the window was.  ``in_system_at_open`` (requests queued
    or in a slot when the window opens) is set beside Little's law,
    ``little_in_system``: the rate times a request's mean time in the
    system, as its mean time to first token plus its mean answer's gaps at
    the window's mean gap between tokens."""
    w = rec["window_s"]
    due = [r for r in rec["requests"] if 0.0 <= r["due"] < w]
    first = [r["first"] - r["due"] for r in due
             if r["first"] is not None and r["first"] <= w]
    gaps = [b - a for r in rec["requests"]
            for a, b in zip(r["tokens"], r["tokens"][1:]) if a >= 0 and b <= w]
    little = None
    if first and gaps:
        n_out = sum(r["max_new"] for r in due) / len(due)
        life = sum(first) / len(first) + (n_out - 1) * sum(gaps) / len(gaps)
        little = len(due) / w * life
    sampled = sum(1 for r in rec["requests"] for t in r["tokens"]
                  if 0.0 <= t < w)
    return {"due": len(due), "in_system_at_open": rec["in_system_at_open"],
            "prompt_tokens_in_window": rec["prompt_tokens"],
            "sampled_tokens_in_window": sampled,
            "little_in_system": little,
            "queued_at_mid": rec["queue_at_mid"],
            "queued_at_close": rec["queue"],
            "busy_slots_at_close": rec["occupancy"],
            "generator_lag_s": rec["lag_s"],
            "compilations_in_window": rec["compiles"],
            "served_past_close_s": rec["past_close_s"]}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float) -> dict:
    """One run of one cell on the chip; returns the result line."""
    cell = resolve(workload)
    device = check_device(cell.chips)
    enable_cache()
    out = window(cell, seed, seconds, trace, t_start=t_start)
    return result(cell, device, out, seed, trace)
