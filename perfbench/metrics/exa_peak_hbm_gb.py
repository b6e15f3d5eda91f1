"""Reader of ``exa_peak_hbm_gb``: see ``perfbench/layers_serve.py``."""

from perfbench.layers_serve import peak_hbm_gb as read  # noqa: F401
