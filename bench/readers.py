"""Arithmetic shared by the metric readers in ``bench/metrics/``.

A reader gets the run record of :class:`bench.serving.Loop` (times in
seconds from the window's start) and returns a number, or None when it
finds nothing to read (no device trace, as on the CPU): then the metric
is left out of the result line.
"""
from __future__ import annotations

from bench import counts, peaks, trace_reduce


def percentile(xs, q: float):
    """Nearest-rank percentile, q in [0, 100]; None on empty input."""
    if not xs:
        return None
    xs = sorted(xs)
    i = min(len(xs) - 1, max(0, int(round(q / 100.0 * (len(xs) - 1)))))
    return float(xs[i])


def _due(rec):
    w = rec["window_s"]
    return [r for r in rec["requests"] if 0.0 <= r["due"] < w]


def _until(t, rec):
    """A time the window never saw counts as the window's end."""
    w = rec["window_s"]
    return w if t is None or t > w else t


def ttft_p95_ms(rec):
    w = rec["window_s"]
    xs = [_until(r["first"], rec) - r["due"] for r in _due(rec)]
    xs += [w - t for t in rec["rejected"] if 0.0 <= t < w]
    p = percentile(xs, 95)
    return None if p is None else p * 1e3


def queue_wait_p95_ms(rec):
    p = percentile([_until(r["admit"], rec) - r["due"] for r in _due(rec)], 95)
    return None if p is None else p * 1e3


def itl_p95_ms(rec):
    w = rec["window_s"]
    gaps = [b - a for r in rec["requests"]
            for a, b in zip(r["tokens"], r["tokens"][1:]) if a >= 0 and b <= w]
    p = percentile(gaps, 95)
    return None if p is None else p * 1e3


def setup_s(rec):
    return rec["setup_s"]


def idle_share(rec):
    tr = rec["trace"]
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def engine_host_ms(rec):
    """Mean over engine steps of the step's host span minus the device busy
    time inside it: host time the device did not hide."""
    tr = rec["trace"]
    if not tr:
        return None
    xs = [(e - s) - trace_reduce.overlap(tr["busy"], s, e)
          for n, s, e in tr["spans"] if n == "bench.engine_step"]
    return 1e3 * sum(xs) / len(xs) if xs else None


def module_s(rec, program: str, kind: str):
    """Device seconds of each run of ``program`` (the jitted function's
    name) in the traced window.  A device trace that lacks the program
    while the record says that steps of its ``kind`` ran in the window is
    an error, not a metric to leave out: the program's name has changed."""
    tr = rec["trace"]
    if not tr:
        return []
    out = [d for name, ds in tr["modules"].items()
           if name in (f"jit_{program}", program) for d in ds]
    if not out and any(s.kind == kind for s in rec["steps"]):
        raise LookupError(f"no program {program!r} in the device trace, "
                          f"where {kind} steps ran; programs: "
                          f"{sorted(tr['modules'])}")
    return out


def _mean(xs):
    return sum(xs) / len(xs) if xs else None


def decode_ms(rec):
    m = _mean(module_s(rec, "decode_fn", "decode"))
    return None if m is None else m * 1e3


def prefill_ms(rec):
    m = _mean(module_s(rec, "prefill_fn", "prefill"))
    return None if m is None else m * 1e3


def _decode_steps(rec):
    return [s for s in rec["steps"] if s.kind == "decode"]


def decode_roofline(rec):
    """Least time of a decode step at the chip's memory bandwidth (the
    bound: a decode step's bytes outweigh its operations at this peak's
    ratio) over its device time, in %."""
    t, steps = _mean(module_s(rec, "decode_fn", "decode")), _decode_steps(rec)
    if not t or not steps:
        return None
    cfg, pk = rec["config"], peaks.lookup(rec["device_kind"])
    need = _mean([counts.decode_bytes(cfg, s.lengths) for s in steps])
    fl = _mean([counts.decode_flops(cfg, s.lengths) for s in steps])
    least = max(need / pk["hbm_bytes_s"], fl / pk["bf16_flops_s"])
    return 100.0 * least / t


def decode_mfu(rec):
    t, steps = _mean(module_s(rec, "decode_fn", "decode")), _decode_steps(rec)
    if not t or not steps:
        return None
    cfg, pk = rec["config"], peaks.lookup(rec["device_kind"])
    fl = _mean([counts.decode_flops(cfg, s.lengths) for s in steps])
    return 100.0 * fl / pk["bf16_flops_s"] / t
