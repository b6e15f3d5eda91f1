"""Reader of ``ling_latent_write_device_share``: see ``perfbench/layers_moe.py``."""

from perfbench.layers_moe import latent_write_device_share as read  # noqa: F401
