"""swap_dispatch_s (program span): per swap in the window, the
``repro.train.dispatch`` of the step that rebuilt the step program (trace,
lowering and compile or cache load of the new program, then the
enqueue); the mean over the swaps."""

from bench import program_spans as ps


def read(rec):
    return ps.mean_seconds(
        ps.children(rec, "repro.train.dispatch", swaps=True))
