"""Reader of ``exa_serve_mfu``: see ``perfbench/layers_serve.py``."""

from perfbench.layers_serve import serve_mfu as read  # noqa: F401
