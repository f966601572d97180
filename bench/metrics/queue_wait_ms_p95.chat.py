"""p95 of a request's wait from submit to admission:
``program_spans.queue_wait_ms_p95``."""
from bench.program_spans import queue_wait_ms_p95 as read  # noqa: F401
