"""Reader of ``exa_idle_in_fetch_ms``: see ``perfbench/layers_moe.py``."""

from perfbench.layers_moe import idle_in_fetch_ms as read  # noqa: F401
