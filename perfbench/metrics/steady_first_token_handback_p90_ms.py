"""Reader of ``steady_first_token_handback_p90_ms``: see ``perfbench/layers_spans.py``."""

from perfbench.layers_spans import first_token_handback_p90_ms as read  # noqa: F401
