"""Reader of ``ling_tpot_p95_ms``: see ``perfbench/layers_serve.py``."""

from perfbench.layers_serve import tpot_p95_ms as read  # noqa: F401
