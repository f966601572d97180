"""Share of the device's busy time in the Mamba-2 mixers (scope
``ssm_mixer``) of the doc-chat cell: ``hybrid_scopes.ssm_device_share``."""
from bench.hybrid_scopes import ssm_device_share as read  # noqa: F401
