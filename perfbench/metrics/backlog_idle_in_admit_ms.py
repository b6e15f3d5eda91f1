"""Reader of ``backlog_idle_in_admit_ms``: see ``perfbench/layers_spans.py``."""

from perfbench.layers_spans import idle_in_admit_ms as read  # noqa: F401
