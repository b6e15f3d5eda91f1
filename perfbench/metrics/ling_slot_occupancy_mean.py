"""Reader of ``ling_slot_occupancy_mean``: see ``perfbench/layers_serve.py``."""

from perfbench.layers_serve import slot_occupancy_mean as read  # noqa: F401
