"""batch_make_s (program span): the mean ``repro.data.batch`` (one
``SyntheticLMDataset.batch`` on a prefetch thread) of the threads that the
window's ``Trainer.run`` calls started, finished batches only."""

from bench import program_spans as ps


def read(rec):
    w = ps.window(rec)
    return ps.mean_seconds(w["batches"]) if w else None
