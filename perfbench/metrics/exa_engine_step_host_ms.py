"""Reader of ``exa_engine_step_host_ms``: see ``perfbench/layers_serve.py``."""

from perfbench.layers_serve import engine_step_host_ms as read  # noqa: F401
