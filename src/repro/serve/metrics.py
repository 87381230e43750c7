"""Per-request and engine-level serving metrics, and the engine's spans.

Request metrics follow the standard serving vocabulary: slot wait (submit
→ a batch slot granted), prefill wait (slot → first prefill chunk), TTFT
(submit → first token sampled), ITL (gap between consecutive sampled
tokens).  Engine metrics count what the scheduler actually did: step mix,
batch occupancy, pool utilization, preemptions, straggler flags, KV pages
live against pages read, and prefill chunks owed.  Engine counters are
monotone totals: a reader takes their difference over its window.

Spans (:func:`span`) put the engine's host phases on the profiler's clock,
the one its device events use, so a device idle gap can be named by the
host phase that caused it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from jax.profiler import TraceAnnotation


def span(name: str, **args) -> TraceAnnotation:
    """A host span ``name`` (``serve.*``) for ``with``; ``args`` become the
    span's arguments in the trace.  With no profiler running it records
    nothing and costs what an inactive ``TraceMe`` costs."""
    return TraceAnnotation(name, **args)


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]); 0.0 on empty input."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    i = min(len(xs) - 1, max(0, int(round(q / 100.0 * (len(xs) - 1)))))
    return float(xs[i])


def summarize_ms(xs) -> dict:
    return {"p50": percentile(xs, 50) * 1e3, "p99": percentile(xs, 99) * 1e3}


@dataclasses.dataclass
class RequestMetrics:
    submit_time: float = 0.0
    slot_time: Optional[float] = None       # a batch slot was granted
    admit_time: Optional[float] = None      # first prefill chunk ran
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    token_times: list = dataclasses.field(default_factory=list)

    def on_slot(self, now: float) -> None:
        if self.slot_time is None:      # kept across preemptions
            self.slot_time = now

    def on_admit(self, now: float) -> None:
        if self.admit_time is None:
            self.admit_time = now

    def on_token(self, now: float) -> None:
        if self.first_token_time is None:
            self.first_token_time = now
        self.token_times.append(now)

    @property
    def ttft(self) -> Optional[float]:
        return (None if self.first_token_time is None
                else self.first_token_time - self.submit_time)

    @property
    def itls(self) -> list:
        t = self.token_times
        return [b - a for a, b in zip(t, t[1:])]


@dataclasses.dataclass
class EngineMetrics:
    steps: int = 0
    decode_steps: int = 0
    prefill_chunks: int = 0
    tokens_sampled: int = 0
    preemptions: int = 0
    timeouts: int = 0
    stragglers: int = 0
    peak_in_flight: int = 0
    occupancy_sum: float = 0.0
    pool_util_sum: float = 0.0
    # KV pages over decode steps: live ones (each active slot's pages with
    # the token being written) and those the step's read path touches
    kv_pages_live: int = 0
    kv_pages_read: int = 0
    # prefill chunks owed at the start of each step (slots in prefill and
    # the queue); over ``steps`` its mean is the prefill backlog
    prefill_backlog_chunks: int = 0

    def on_step(self, kind: str, occupancy: float, pool_util: float,
                backlog_chunks: int) -> None:
        self.steps += 1
        if kind == "decode":
            self.decode_steps += 1
        elif kind == "prefill":
            self.prefill_chunks += 1
        self.occupancy_sum += occupancy
        self.pool_util_sum += pool_util
        self.prefill_backlog_chunks += backlog_chunks

    def on_decode(self, pages_live: int, pages_read: int) -> None:
        self.kv_pages_live += pages_live
        self.kv_pages_read += pages_read

    @property
    def occupancy_mean(self) -> float:
        return self.occupancy_sum / self.steps if self.steps else 0.0

    @property
    def pool_util_mean(self) -> float:
        return self.pool_util_sum / self.steps if self.steps else 0.0

    def summary(self) -> dict:
        return {
            "steps": self.steps,
            "decode_steps": self.decode_steps,
            "prefill_chunks": self.prefill_chunks,
            "tokens_sampled": self.tokens_sampled,
            "preemptions": self.preemptions,
            "timeouts": self.timeouts,
            "stragglers": self.stragglers,
            "peak_in_flight": self.peak_in_flight,
            "kv_pages_live": self.kv_pages_live,
            "kv_pages_read": self.kv_pages_read,
            "prefill_backlog_chunks": self.prefill_backlog_chunks,
            "occupancy_mean": round(self.occupancy_mean, 4),
            "pool_util_mean": round(self.pool_util_mean, 4),
        }
