"""Reader of ``exa_moe_experts_roofline``: see ``perfbench/layers_kda.py``."""

from perfbench.layers_kda import moe_experts_roofline as read  # noqa: F401
