"""Reader of ``ling_kda_prefill_device_share``: see ``perfbench/layers_kda.py``."""

from perfbench.layers_kda import kda_prefill_device_share as read  # noqa: F401
