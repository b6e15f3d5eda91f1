"""Reader of ``steady_admit_to_first_token_p90_ms``: see ``perfbench/layers_spans.py``."""

from perfbench.layers_spans import admit_to_first_token_p90_ms as read  # noqa: F401
