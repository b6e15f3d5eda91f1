"""Reader of ``steady_queue_wait_p90_ms``: see ``perfbench/layers_spans.py``."""

from perfbench.layers_spans import queue_wait_p90_ms as read  # noqa: F401
