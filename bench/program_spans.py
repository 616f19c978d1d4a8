"""The program's own spans of a run's window, for the readers of the
``program_span`` metrics.

``repro.obs.spans`` holds, in the benchmark's process, every span the
program opened.  The window's steps are the ``repro.train.step`` spans
numbered above the set-up's (``rec["attempted"] - rec["steps"]``); the
window's ``Trainer.run`` calls are their parents, and the window's
prefetch threads' batches are the ``repro.data.batch`` spans those calls
parent.  A program without the store, a store that dropped a span, or a
window with no step gives None, and so does every reader.
"""

from __future__ import annotations

from typing import List, Optional


def window(rec: dict) -> Optional[dict]:
    """``{"steps": [{child name: span}], "batches": [span]}`` of the
    window: per closed window step its children by name, and every batch
    span the window's threads opened (``end_ns`` None while in the
    making)."""
    try:
        from repro.obs import spans
    except ImportError:
        return None
    snap = spans.snapshot()
    if snap["dropped"]:
        return None
    first = rec["attempted"] - rec["steps"]
    held = snap["spans"]
    steps = {s["id"]: {} for s in held
             if s["name"] == "repro.train.step" and s["end_ns"] is not None
             and s["attrs"]["step"] > first}
    if not steps:
        return None
    runs = set()
    for s in held:
        if s["id"] in steps:
            runs.add(s["parent"])
        elif s["parent"] in steps:
            steps[s["parent"]][s["name"]] = s
    return {"steps": list(steps.values()),
            "batches": [s for s in held if s["name"] == "repro.data.batch"
                        and s["parent"] in runs]}


def mean_seconds(spans: List[dict]) -> Optional[float]:
    """The mean duration of the closed spans given, or None."""
    done = [(s["end_ns"] - s["start_ns"]) * 1e-9
            for s in spans if s["end_ns"] is not None]
    return sum(done) / len(done) if done else None


def children(rec: dict, name: str, *, swaps: bool = False) -> List[dict]:
    """The window steps' child spans called ``name``; with ``swaps``,
    only those of the steps that rebuilt the step program after a
    ``link.replace()``."""
    w = window(rec)
    if w is None:
        return []
    return [k[name] for k in w["steps"] if name in k and
            (not swaps or "repro.train.rebuild" in k)]
