"""Reader of ``ling_expert_load_max_over_mean``: see ``perfbench/layers_kda.py``."""

from perfbench.layers_kda import expert_load_max_over_mean as read  # noqa: F401
