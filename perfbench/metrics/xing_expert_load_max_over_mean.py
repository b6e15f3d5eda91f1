"""Reader of ``xing_expert_load_max_over_mean``: see ``perfbench/layers_moe.py``."""

from perfbench.layers_moe import expert_load_max_over_mean as read  # noqa: F401
