"""tokens_per_s (host clock): training tokens completed in the window over
the window's wall time, batch preparation and every gap included."""


def read(rec):
    return rec["tokens"] / rec["window_s"] if rec["steps"] else None
