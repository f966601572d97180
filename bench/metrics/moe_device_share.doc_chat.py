"""Share of the device's busy time in the expert FFNs (scopes
``moe_router``, ``moe_experts``, ``shared_expert``) of the doc-chat
cell: ``hybrid_scopes.moe_device_share``."""
from bench.hybrid_scopes import moe_device_share as read  # noqa: F401
