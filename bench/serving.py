"""One measured window of the serving engine, shared by the arrival modules.

Set-up builds the weights (``bench.weights``, one jit), the program's
``ServeEngine`` for the ``ModelConfig`` that the configuration's
architecture module gives (``program_config``) at the configuration's
geometry, and compiles its two step programs by serving one request of two
prefill chunks.  Then the mix's traffic runs for ``warmup_s`` (set-up: it
fills the slots to a steady occupancy) and the window opens for
``seconds``.  An arrival module (``bench/arrivals``) decides only when
requests are submitted (:class:`Loop` ``feed``).

A request is timed from its due time: the engine is given it as
``arrival``, so queue wait and time to first token include any lateness of
the loop itself, which is reported as ``lag_s``.

With ``trace`` the window is at most ``TRACE_S`` long and runs under the
JAX profiler (Python tracer off), with host spans from this file:
``bench.window`` around the window, ``bench.engine_step`` around each
``engine.step()``, ``bench.call.decode`` / ``bench.call.prefill`` around
the engine's calls of its jitted steps, and ``bench.wait`` while idle.  Each
engine step is also recorded with the live lengths it ran over, for the
architecture module's counts.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import tempfile
import time

import jax
import numpy as np

from bench import harness, trace_reduce, weights

TRACE_S = 10.0
PAST_CLOSE_S = 60.0    # longest the check waits for served tokens
WARM_TOKENS = 2        # tokens of the compile warm-up request


def check_layout(params, mcfg) -> None:
    """The benchmark's weights must have the program's tree, shapes and
    types, or the program would be handed something else than it serves."""
    from repro.models import api
    want = jax.tree.map(lambda a: (a.shape, a.dtype), api.abstract_params(mcfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if got != want:
        raise SystemExit(f"weights layout differs from the program's: "
                         f"{got} != {want}")


@dataclasses.dataclass
class Step:
    kind: str                 # prefill | decode
    t0: float
    t1: float
    lengths: list             # decode: cached tokens of each active slot


class Loop:
    """Drives one engine from warm-up through the window."""

    def __init__(self, ctx, feed):
        from repro.serve import ServeEngine
        self.ctx = ctx
        geo = ctx.cfg["serve"]
        self.mcfg = harness.arch(ctx.cfg).program_config(ctx.cfg)
        self.params = weights.make(ctx.cfg, ctx.seed)
        check_layout(self.params, self.mcfg)
        self.eng = ServeEngine(
            self.mcfg, self.params, slots=geo["slots"],
            max_len=geo["max_len"], page_size=geo["page_size"],
            prefill_chunk=geo["prefill_chunk"], attn_read=geo["attn_read"])
        if ctx.hook is not None:
            ctx.hook(self.eng)
        self.feed = feed
        self.requests = []        # every submitted Request
        self.rejected = []        # due times of backpressured submissions
        self.steps: list[Step] = []
        self.lag = 0.0
        self.w0 = self.w1 = float("inf")
        self.in_system_at_open = self.queue_at_mid = None
        self._warm_compile()

    def _warm_compile(self) -> None:
        """Compile the prefill and decode programs through the engine's own
        call path (two chunks, so the second reads the first's pages)."""
        n = self.eng.prefill_chunk + 1
        self.eng.submit(np.arange(n) % self.mcfg.vocab_size,
                        max_new_tokens=WARM_TOKENS)
        self.eng.run()
        jax.block_until_ready(self.eng.cache)
        self.eng.finished.clear()

    def submit(self, spec, due_abs: float):
        """Submit ``spec`` as due at ``due_abs``; returns the engine's
        request, or None when the engine pushed back."""
        from repro.serve.engine import Backpressure
        if due_abs >= self.w0:
            self.lag = max(self.lag, time.monotonic() - due_abs)
        try:
            r = self.eng.submit(spec.prompt, temperature=spec.temperature,
                                seed=spec.seed, max_new_tokens=spec.max_new,
                                arrival=due_abs)
        except Backpressure:
            self.rejected.append(due_abs)
            return None
        self.requests.append(r)
        return r

    def _traced_step(self) -> bool:
        from repro.serve.scheduler import RequestState
        self.eng.sched.admit()      # what the step does first; see who decodes
        dec = [r.cache_len for r in self.eng.sched.live()
               if r.state is RequestState.DECODE]
        m = self.eng.metrics
        nd, npf = m.decode_steps, m.prefill_chunks
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            ran = self.eng.step()
        t1 = time.monotonic()
        if m.decode_steps > nd:
            self.steps.append(Step("decode", t0, t1, dec))
        elif m.prefill_chunks > npf:
            self.steps.append(Step("prefill", t0, t1, []))
        return ran

    def _wrap_calls(self) -> None:
        steps = self.eng.steps

        def wrap(name, fn):
            def call(*a):
                with jax.profiler.TraceAnnotation(name):
                    return fn(*a)
            return call

        self.eng.steps = dataclasses.replace(
            steps, decode=wrap("bench.call.decode", steps.decode),
            prefill=wrap("bench.call.prefill", steps.prefill))

    def run(self) -> dict:
        ctx = self.ctx
        trace = bool(ctx.trace)
        seconds = window_seconds(ctx)
        t0 = time.monotonic()
        w0 = self.w0 = t0 + ctx.mix["warmup_s"]
        w1 = self.w1 = w0 + seconds
        tracer = _Tracer() if trace else None
        if trace:
            self._wrap_calls()
        step = self._traced_step if trace else self.eng.step
        setup_s = None
        while True:
            now = time.monotonic()
            if setup_s is None:
                if now >= w0:
                    setup_s = time.time() - ctx.t_start
                    n_compiles = ctx.compiles.n
                    self.in_system_at_open = self._in_system()
                    prompt_at_open = self._prompt_tokens()
                    if tracer:
                        tracer.open_window()
                elif tracer and now >= w0 - 1.0:
                    tracer.start()
            elif self.queue_at_mid is None and now >= w0 + seconds / 2:
                self.queue_at_mid = len(self.eng.sched.queue)
            if now >= w1:
                break
            self.feed(self, now, w0)
            if not step():
                nxt = min(self.feed.next_due(w0), w1 if setup_s else w0)
                with _span(trace, "bench.wait"):
                    time.sleep(max(0.0, nxt - time.monotonic()))
        prompt_in_window = self._prompt_tokens() - prompt_at_open
        jax.block_until_ready(self.eng.cache)
        n_compiles = ctx.compiles.n - n_compiles
        summary = tracer.stop() if tracer else None
        self.record = self._record(setup_s, seconds, summary)
        self.record["compiles"] = n_compiles
        self.record["prompt_tokens"] = prompt_in_window
        self.record["past_close_s"] = self._serve_past_close(ctx.min_tokens)
        return self.record

    def _serve_past_close(self, min_tokens: int) -> float:
        """Step on past the window's close, submitting nothing, until the
        greedy requests hold ``min_tokens`` served tokens or a minute has
        passed, so that the check has as many tokens to compare after a
        traced window (10 s) as after a full one: in a long-document cell
        few answers are far along 10 s after they start.  The metrics are
        the record's, taken at the close.  Returns the seconds served."""
        t0 = time.monotonic()
        while (self._greedy_tokens() < min_tokens
               and time.monotonic() - t0 < PAST_CLOSE_S and self.eng.step()):
            pass
        jax.block_until_ready(self.eng.cache)
        return time.monotonic() - t0

    def _prompt_tokens(self) -> int:
        """Prompt tokens prefilled so far, over every request (one that
        is preempted starts again from none: the pool of a cell is sized so
        that none is)."""
        return sum(min(r.cache_len, len(r.prompt)) for r in self.requests)

    def _greedy_tokens(self) -> int:
        return sum(len(r.out_tokens) for r in self.requests
                   if r.params.temperature == 0.0)

    def _record(self, setup_s, seconds, summary) -> dict:
        w0, w1 = self.w0, self.w1
        reqs = []
        for r in self.requests:
            m = r.metrics
            reqs.append(dict(
                due=m.submit_time - w0, admit=_rel(m.admit_time, w0),
                first=_rel(m.first_token_time, w0),
                finish=_rel(m.finish_time, w0),
                tokens=[t - w0 for t in m.token_times],
                state=r.state.value, n_prompt=len(r.prompt),
                n_out=len(r.out_tokens), max_new=r.params.max_new_tokens,
                temperature=r.params.temperature))
        steps = [dataclasses.replace(s, t0=s.t0 - w0, t1=s.t1 - w0)
                 for s in self.steps if w0 <= s.t0 < w1]
        return dict(window_s=seconds, setup_s=setup_s, lag_s=self.lag,
                    in_system_at_open=self.in_system_at_open,
                    queue_at_mid=self.queue_at_mid,
                    requests=reqs, rejected=[t - w0 for t in self.rejected],
                    steps=steps, trace=summary,
                    occupancy=self.eng.sched.occupancy(),
                    queue=len(self.eng.sched.queue),
                    slots=self.eng.n_slots)

    def _in_system(self) -> int:
        return len(self.eng.sched.queue) + self.eng.sched.occupancy()

    def served_greedy(self) -> list:
        """(prompt, tokens served) of every greedy request that was served
        a token, finished or not, for the correctness check: served by the
        window's close, or past it where the check needs more
        (:meth:`_serve_past_close`)."""
        return [(np.asarray(r.prompt, np.int32),
                 np.asarray(r.out_tokens, np.int32))
                for r in self.requests
                if r.params.temperature == 0.0 and r.out_tokens]

    def release(self) -> None:
        """Drop the program's state so the reference runs on a free chip."""
        self.eng = None
        self.params = None
        self.requests = []


def _rel(t, w0):
    return None if t is None else t - w0


def window_seconds(ctx) -> float:
    return min(ctx.seconds, TRACE_S) if ctx.trace else ctx.seconds


def _span(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


class _Tracer:
    """The JAX profiler around the window, Python tracer off.  The trace is
    written to a temporary directory, reduced, and deleted; with
    ``BENCH_KEEP_TRACE=<dir>`` it is copied there first."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.started = False
        self.window = None

    def start(self) -> None:
        if not self.started:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.started = True

    def open_window(self) -> None:
        self.start()
        # made after the profiler starts: an annotation made before it
        # records nothing
        self.window = jax.profiler.TraceAnnotation("bench.window")
        self.window.__enter__()

    def stop(self):
        self.window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            summary = trace_reduce.reduce_dir(self.dir)
            keep = os.environ.get("BENCH_KEEP_TRACE")
            if keep:
                shutil.copytree(self.dir, keep, dirs_exist_ok=True)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return summary
