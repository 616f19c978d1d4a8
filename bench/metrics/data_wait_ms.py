"""data_wait_ms (program span): per window step, its ``repro.data.wait``
(the trainer waiting on the prefetch thread for the next batch); the
mean, in milliseconds."""

from bench import program_spans as ps


def read(rec):
    s = ps.mean_seconds(ps.children(rec, "repro.data.wait"))
    return None if s is None else 1e3 * s
