"""Model FLOP/s utilisation of serving: ``layer_readers.serve_mfu``."""
from bench.layer_readers import serve_mfu as read  # noqa: F401
