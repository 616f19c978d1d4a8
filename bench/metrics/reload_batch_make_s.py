"""reload_batch_make_s (program span): ``batch_make_s`` read in the reload
cell, where the batch time moves ``reload_to_step_s``."""

from bench.metrics.batch_make_s import read  # noqa: F401
