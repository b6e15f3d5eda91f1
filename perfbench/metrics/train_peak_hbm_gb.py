"""Reader of ``train_peak_hbm_gb``: see ``perfbench/layers_train.py``."""

from perfbench.layers_train import peak_hbm_gb as read  # noqa: F401
