"""Reader of ``backlog_kv_write_device_share``: see ``perfbench/layers_spans.py``."""

from perfbench.layers_spans import kv_write_device_share as read  # noqa: F401
