"""Reader of ``flash_attn_roofline``: see ``perfbench/layers_train.py``."""

from perfbench.layers_train import flash_attn_roofline as read  # noqa: F401
