"""Queue wait before admission, chat cells."""
from bench.readers import queue_wait_p95_ms as read  # noqa: F401
