"""Reader of ``xing_hc_mix_device_share``: see ``perfbench/layers_moe.py``."""

from perfbench.layers_moe import hc_mix_device_share as read  # noqa: F401
