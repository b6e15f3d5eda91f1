"""Reader of ``exa_swa_prefill_roofline``: see ``perfbench/layers_swa.py``."""

from perfbench.layers_swa import swa_prefill_roofline as read  # noqa: F401
