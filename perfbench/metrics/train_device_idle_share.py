"""Reader of ``train_device_idle_share``: see ``perfbench/layers_train.py``."""

from perfbench.layers_train import device_idle_share as read  # noqa: F401
