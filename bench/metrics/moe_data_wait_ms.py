"""moe_data_wait_ms: ``data_wait_ms`` read in the Moonlight training cell, where it
moves that cell's ``tokens_per_s``."""

from bench.metrics.data_wait_ms import read  # noqa: F401
