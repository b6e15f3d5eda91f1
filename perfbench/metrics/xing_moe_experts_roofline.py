"""Reader of ``xing_moe_experts_roofline``: see ``perfbench/layers_moe.py``."""

from perfbench.layers_moe import moe_experts_roofline as read  # noqa: F401
