"""The host's share of a scheduler step, read from the program's own
step spans: ``program_spans.step_host_ms``."""
from bench.program_spans import step_host_ms as read  # noqa: F401
