"""Arrival processes: a traffic mix's ``arrivals`` key names one of these
modules, whose ``run(ctx)`` returns a :class:`bench.serving.Loop` that has
run its window."""
