"""Reader of ``exa_decode_step_device_ms``: see ``perfbench/layers_serve.py``."""

from perfbench.layers_serve import decode_step_device_ms as read  # noqa: F401
