"""Reader of ``ling_mla_decode_device_share``: see ``perfbench/layers_moe.py``."""

from perfbench.layers_moe import mla_decode_device_share as read  # noqa: F401
