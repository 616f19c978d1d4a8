"""device_wait_ms (program span): per window step, its
``repro.train.device_wait`` (the host blocked on the step's loss after
the enqueue); the mean, in milliseconds."""

from bench import program_spans as ps


def read(rec):
    s = ps.mean_seconds(ps.children(rec, "repro.train.device_wait"))
    return None if s is None else 1e3 * s
