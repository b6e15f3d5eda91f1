"""Reader of ``steady_prefill_useful_token_share``: see ``perfbench/layers_spans.py``."""

from perfbench.layers_spans import prefill_useful_token_share as read  # noqa: F401
