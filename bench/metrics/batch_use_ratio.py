"""batch_use_ratio (program span): the window's steps over the batches
that its ``Trainer.run`` calls' prefetch threads started
(``repro.data.batch`` spans, in the making included), in percent.  Each
call's thread drops the batch it is making when the call ends."""

from bench import program_spans as ps


def read(rec):
    w = ps.window(rec)
    if not w or not w["batches"]:
        return None
    return 100.0 * len(w["steps"]) / len(w["batches"])
