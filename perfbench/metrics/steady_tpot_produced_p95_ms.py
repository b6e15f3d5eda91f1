"""Reader of ``steady_tpot_produced_p95_ms``: see ``perfbench/layers_spans.py``."""

from perfbench.layers_spans import tpot_produced_p95_ms as read  # noqa: F401
