"""Reader of ``backlog_decode_roofline``: see ``perfbench/layers_serve.py``."""

from perfbench.layers_serve import decode_roofline as read  # noqa: F401
