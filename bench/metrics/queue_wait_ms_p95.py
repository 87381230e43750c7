"""95th percentile of queue wait (due time to first prefill chunk) over
requests due in the window; scheduler layer."""
from bench.readers import queue_wait_p95_ms as read  # noqa: F401
