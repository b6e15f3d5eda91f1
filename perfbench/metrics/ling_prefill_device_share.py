"""Reader of ``ling_prefill_device_share``: see ``perfbench/layers_serve.py``."""

from perfbench.layers_serve import prefill_device_share as read  # noqa: F401
