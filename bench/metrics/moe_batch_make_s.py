"""moe_batch_make_s: ``batch_make_s`` read in the Moonlight training cell, where it
moves that cell's ``tokens_per_s``."""

from bench.metrics.batch_make_s import read  # noqa: F401
