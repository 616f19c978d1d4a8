"""retrace_s (program span): per swap, the sum of JAX's own trace, lowering
and backend compile (cache load included) durations, as jax.monitoring
reports them between the swap and the end of its first step; the mean
over the window's swaps."""


def read(rec):
    s = [x["retrace_s"] for x in rec["swaps"]]
    return sum(s) / len(s) if s else None
