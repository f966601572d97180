"""Share of the TT-HF step's device time spent in D2D mixing and global
aggregation: ``program_spans.sync_device_share``."""
from bench.program_spans import sync_device_share as read  # noqa: F401
