"""Reader of ``ling_experts_touched_mean``: see ``perfbench/layers_moe.py``."""

from perfbench.layers_moe import experts_touched_mean as read  # noqa: F401
