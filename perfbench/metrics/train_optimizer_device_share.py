"""Reader of ``train_optimizer_device_share``: see ``perfbench/layers_spans.py``."""

from perfbench.layers_spans import optimizer_device_share as read  # noqa: F401
