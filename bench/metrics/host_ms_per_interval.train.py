"""Host time per trainer interval with the device idle:
``program_spans.host_ms_per_interval``."""
from bench.program_spans import host_ms_per_interval as read  # noqa: F401
