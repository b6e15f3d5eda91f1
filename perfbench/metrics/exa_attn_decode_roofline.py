"""Reader of ``exa_attn_decode_roofline``: see ``perfbench/layers_swa.py``."""

from perfbench.layers_swa import attn_decode_roofline as read  # noqa: F401
