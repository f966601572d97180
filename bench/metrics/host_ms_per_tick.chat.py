"""The host's share of a serving tick: ``layer_readers.host_ms_per_tick``."""
from bench.layer_readers import host_ms_per_tick as read  # noqa: F401
