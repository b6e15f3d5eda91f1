"""Reader of ``ling_kda_decode_roofline``: see ``perfbench/layers_kda.py``."""

from perfbench.layers_kda import kda_decode_roofline as read  # noqa: F401
