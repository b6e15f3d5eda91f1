"""Reader of ``xing_decode_roofline``: see ``perfbench/layers_moe.py``."""

from perfbench.layers_moe import decode_roofline as read  # noqa: F401
