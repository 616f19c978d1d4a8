"""swap_data_wait_s (program span): per swap in the window, the
``repro.data.wait`` of the step that rebuilt the step program (a fresh
prefetch thread's first batch); the mean over the swaps."""

from bench import program_spans as ps


def read(rec):
    return ps.mean_seconds(ps.children(rec, "repro.data.wait", swaps=True))
