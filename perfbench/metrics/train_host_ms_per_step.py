"""Reader of ``train_host_ms_per_step``: see ``perfbench/layers_train.py``."""

from perfbench.layers_train import host_ms_per_step as read  # noqa: F401
