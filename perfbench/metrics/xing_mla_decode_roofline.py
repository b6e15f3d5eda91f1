"""Reader of ``xing_mla_decode_roofline``: see ``perfbench/layers_moe.py``."""

from perfbench.layers_moe import mla_decode_roofline as read  # noqa: F401
