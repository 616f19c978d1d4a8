"""moe_device_idle_share: ``device_idle_share`` read in the Moonlight training cell, where it
moves that cell's ``tokens_per_s``."""

from bench.metrics.device_idle_share import read  # noqa: F401
