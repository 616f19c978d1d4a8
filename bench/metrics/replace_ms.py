"""replace_ms (host clock): the benchmark's span around each
`PolicyLink.replace` call (verify, tier compile, T3 flush); the mean over
the window's swaps, in milliseconds."""


def read(rec):
    s = [x["replace_s"] for x in rec["swaps"]]
    return 1e3 * sum(s) / len(s) if s else None
