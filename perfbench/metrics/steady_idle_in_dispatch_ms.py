"""Reader of ``steady_idle_in_dispatch_ms``: see ``perfbench/layers_spans.py``."""

from perfbench.layers_spans import idle_in_dispatch_ms as read  # noqa: F401
