"""Reader of ``ling_mla_decode_roofline``: see ``perfbench/layers_kda.py``."""

from perfbench.layers_kda import mla_decode_roofline as read  # noqa: F401
