"""Reader of ``exa_device_idle_share``: see ``perfbench/layers_serve.py``."""

from perfbench.layers_serve import device_idle_share as read  # noqa: F401
