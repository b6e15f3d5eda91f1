"""Reader of ``ling_moe_device_share``: see ``perfbench/layers_moe.py``."""

from perfbench.layers_moe import moe_device_share as read  # noqa: F401
