"""reload_to_step_s (host clock): per `link.replace()` in the window, the
time from the call to the end of the first step on the new policy; the
mean over the window's swaps."""


def read(rec):
    s = [x["reload_to_step_s"] for x in rec["swaps"]]
    return sum(s) / len(s) if s else None
