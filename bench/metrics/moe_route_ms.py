"""moe_route_ms (device trace): per window step, the device time under
the ``repro.moe.route`` scope (router, top-k, sort and un-sort of the
pairs, combine; forward, recomputation and backward), in milliseconds
(bench/scope_reduce.py).  None without the scope."""


def read(rec):
    sc = rec.get("scopes")
    if not sc or not rec["steps"] or \
            not sc["seconds"].get("repro.moe.route"):
        return None
    return 1e3 * sc["seconds"]["repro.moe.route"] / rec["steps"]
