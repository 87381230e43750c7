"""Mean host milliseconds per engine step that the device did not hide: the
bench.engine_step span minus device busy time inside it."""
from bench.readers import engine_host_ms as read  # noqa: F401
