"""Reader of ``exa_decode_roofline``: see ``perfbench/layers_swa.py``."""

from perfbench.layers_swa import decode_roofline as read  # noqa: F401
