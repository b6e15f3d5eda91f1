"""Reader of ``ling_idle_in_prefill_ms``: see ``perfbench/layers_moe.py``."""

from perfbench.layers_moe import idle_in_prefill_ms as read  # noqa: F401
