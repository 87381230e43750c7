"""Open loop: independent users send on the mix's schedule, whether or not
earlier requests have finished, so a slow engine builds a queue."""
from __future__ import annotations

from bench import loadgen, serving


class Feed:
    def __init__(self, specs):
        self.specs = specs
        self.i = 0

    def __call__(self, loop, now: float, w0: float) -> None:
        while self.i < len(self.specs) and w0 + self.specs[self.i].due <= now:
            s = self.specs[self.i]
            loop.submit(s, w0 + s.due)
            self.i += 1

    def next_due(self, w0: float) -> float:
        return (w0 + self.specs[self.i].due if self.i < len(self.specs)
                else float("inf"))


def run(ctx) -> serving.Loop:
    specs = loadgen.open_loop(ctx.mix, ctx.cfg["vocab_size"], ctx.seed,
                              serving.window_seconds(ctx))
    loop = serving.Loop(ctx, Feed(specs))
    loop.run()
    return loop
