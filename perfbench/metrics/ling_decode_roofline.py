"""Reader of ``ling_decode_roofline``: see ``perfbench/layers_kda.py``."""

from perfbench.layers_kda import decode_roofline as read  # noqa: F401
