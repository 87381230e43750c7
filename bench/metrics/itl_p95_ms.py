"""95th percentile (nearest rank) of every gap between consecutive tokens of
every request, both tokens inside the window."""
from bench.readers import itl_p95_ms as read  # noqa: F401
