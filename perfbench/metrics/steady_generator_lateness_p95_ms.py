"""Reader of ``steady_generator_lateness_p95_ms``: see ``perfbench/layers_serve.py``."""

from perfbench.layers_serve import generator_lateness_p95_ms as read  # noqa: F401
