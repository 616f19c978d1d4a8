"""What decides ``correct`` in a training cell.

Three numbers, each against its limit from the configuration file's
``limits``:

  loss_gap    the largest |program - reference| of the loss over the
              checked steps;
  grad_gap    over the leaves, the largest gap between the program's and
              the reference's norm of the first step's clipped gradient,
              as a share of the reference's norm of that leaf or of the
              median leaf, whichever is larger;
  update_gap  the same for the norm of the parameters' change over the
              checked steps.

Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are left out of both leaf gaps.  A
leaf that one side has and the other lacks, or a number that is not
finite, fails the check.
"""

from __future__ import annotations

import math
from typing import Dict

SKIP_BELOW = 1e-3


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep) -> float:
    med = _median([ref[k] for k in keep])
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep]
    if not gaps or not all(math.isfinite(g) for g in gaps):
        return math.inf
    return max(gaps)


def compare(prog: dict, ref: dict, limits: dict) -> Dict[str, dict]:
    """``prog`` and ``ref`` as :func:`bench.reference.transformer.train`
    returns them.  Returns ``{name: {"value", "limit"}}``."""
    inf = math.inf
    out = {}
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = inf
    else:
        loss_gap = max(abs(a - b) for a, b in zip(prog["losses"],
                                                  ref["losses"]))
    out["loss_gap"] = loss_gap
    rg = ref["grad_norms"]
    same = set(prog["grad_norms"]) == set(rg) == \
        set(prog["change_norms"]) == set(ref["change_norms"])
    if same:
        med = _median(list(rg.values()))
        keep = sorted(k for k, v in rg.items() if v >= SKIP_BELOW * med)
        out["grad_gap"] = leaf_gap(prog["grad_norms"], rg, keep)
        out["update_gap"] = leaf_gap(prog["change_norms"],
                                     ref["change_norms"], keep)
    else:
        out["grad_gap"] = out["update_gap"] = inf
    res = {}
    for k, v in out.items():
        v = v if math.isfinite(v) else inf
        res[k] = {"value": v, "limit": limits[k]}
    return res


def passes(compared: Dict[str, dict]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in compared.values())
