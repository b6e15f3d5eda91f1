"""Reader of ``train_step_device_mfu``: see ``perfbench/layers_train.py``."""

from perfbench.layers_train import step_device_mfu as read  # noqa: F401
