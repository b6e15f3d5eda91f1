"""Reader of ``exa_gqa_decode_device_share``: see ``perfbench/layers_swa.py``."""

from perfbench.layers_swa import gqa_decode_device_share as read  # noqa: F401
