"""Device idle share of the traced window: ``layer_readers.idle_share``."""
from bench.layer_readers import idle_share as read  # noqa: F401
