"""Reader of ``ling_idle_in_admit_ms``: see ``perfbench/layers_moe.py``."""

from perfbench.layers_moe import idle_in_admit_ms as read  # noqa: F401
