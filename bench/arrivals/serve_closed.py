"""Closed loop: a fixed number of clients, each sending its next request
the moment its last one ends, so the engine always has work queued, as an
offline batch job keeps it.

The clients start with a request under way each (``bench.loadgen``
``under_way``, with as many as there are clients), and each then draws its
next from one stream of the mix's stratified sizes in the seed's order.
A request is due when the one it follows ended."""
from __future__ import annotations

from bench import loadgen, serving


class Feed:
    def __init__(self, first, stream):
        self.stream = list(stream)
        self.first = list(first)
        self.current = [None] * len(self.first)

    def __call__(self, loop, now: float, w0: float) -> None:
        for c, r in enumerate(self.current):
            if r is None:
                spec = self.first[c]
                due = w0 + spec.due
            elif r.metrics.finish_time is not None:
                if not self.stream:
                    raise RuntimeError("the closed loop's stream ran out; "
                                       "give the mix a longer one")
                spec = self.stream.pop(0)
                due = r.metrics.finish_time
            else:
                continue
            self.current[c] = loop.submit(spec, due)

    def next_due(self, w0: float) -> float:
        return float("inf")


def run(ctx) -> serving.Loop:
    mix = ctx.mix
    first, stream = loadgen.closed_loop(mix, ctx.cfg["vocab_size"], ctx.seed)
    loop = serving.Loop(ctx, Feed(first, stream))
    loop.run()
    return loop
