"""Reduce a ``jax.profiler`` trace of the window to device metrics.

Reads the ``*.xplane.pb`` that ``jax.profiler.start_trace`` writes with
``jax.profiler.ProfileData``.  Per device plane (``/device:TPU:<n>``)
the events of its ``XLA Ops`` line are the operations that ran; each
event's name is the HLO instruction, ``%<name> = <shape> <opcode>(...)``.

* busy: the union of the operations' intervals; idle = window - busy.
* per operation: device time summed over the window, by instruction name,
  of the operations that contain no other (a ``while`` loop is one event
  around its body's).
* collective: operations whose opcode is one of :data:`COLLECTIVE`
  (XLA's collectives, and the ``collective-permute``s that the
  dispatcher's ring and tree algorithms lower to); exposed collective
  time is the part of their union that no other operation overlaps.
* idle gaps: the gaps between operations, each labelled with the host
  span that covers its middle, the device clock being put on the host's
  by matching each module run to the host's ``CompleteCallbacks`` of
  the same ``run_id``.

Numbers are averaged over the device planes (chips).
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

COLLECTIVE = {
    "all-reduce", "all-reduce-start", "all-reduce-done",
    "all-gather", "all-gather-start", "all-gather-done",
    "reduce-scatter", "reduce-scatter-start", "reduce-scatter-done",
    "all-to-all", "ragged-all-to-all", "collective-broadcast",
    "collective-permute", "collective-permute-start",
    "collective-permute-done", "send", "send-done", "recv", "recv-done",
}
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
# host spans that label an idle gap: the benchmark's own first, then
# JAX's dispatch of a jitted call (by prefix) and compilation
LABELS = ("bench.", "PjitFunction")

Interval = Tuple[float, float]


def instruction(text: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0]
    return head.lstrip("%").strip()


def opcode(text: str) -> str:
    """The opcode of an HLO instruction's text (``fusion``, ``all-gather``)."""
    if " = " not in text:
        return instruction(text)
    m = _OPCODE.search(" " + text.split(" = ", 1)[1])
    return m.group(1) if m else instruction(text)


def union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(iv: List[Interval]) -> float:
    return sum(b - a for a, b in iv)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (merged) intervals ``a`` that the merged ``b`` miss."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves(evs: list) -> list:
    """The operations that contain no other (a ``while`` or ``call`` is
    traced as one event around the events of its body)."""
    evs = sorted(evs, key=lambda x: (x[1], -x[2]))
    out = []
    for i, (t, s, e) in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1][1] < e and evs[i + 1][2] <= e:
            continue
        out.append((t, s, e))
    return out


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def load(pd) -> Tuple[Dict[str, list], list, Dict[str, list], list]:
    """From a ProfileData: per device, its ops ``(text, start, end)`` and
    its module runs ``(run_id, start, end)``; the host spans and the
    host's ``CompleteCallbacks`` ``(run_id, start)``; all in ns."""
    ops, mods, host, done = {}, {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CUSTOM"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            ops[plane.name] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in lines["XLA Ops"].events]
            mods[plane.name] = [
                (_stats(e).get("run_id"), e.start_ns,
                 e.start_ns + e.duration_ns)
                for e in (lines["XLA Modules"].events
                          if "XLA Modules" in lines else [])]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == "CompleteCallbacks":
                        done.append((_stats(e).get("run_id"), e.start_ns))
                    if e.duration_ns > 0:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return ops, mods, host, done


def clock_offset(mods: list, done: list) -> Optional[float]:
    """Host minus device clock: the median, over module runs, of the
    host's completion callback start minus the module's end."""
    at = {}
    for rid, s in done:
        at.setdefault(rid, s)
    d = sorted(at[rid] - end for rid, _, end in mods
               if rid is not None and rid in at)
    return d[len(d) // 2] if d else None


def label(mid: float, host: list, labels: tuple = LABELS) -> str:
    """The shortest host span that covers ``mid``, the benchmark's own
    (``labels[0]``) before the others."""
    best = None
    for name, s, e in host:
        if s <= mid <= e and (name.startswith(labels) or
                              "ompile" in name):
            key = (not name.startswith(labels[0]), e - s)
            if best is None or key < best[1]:
                best = (name, key)
    return best[0] if best else "unlabelled"


def reduce(ops: Dict[str, list], mods: Dict[str, list], host: list,
           done: list, *, steps: int, window_s: float,
           labels: tuple = LABELS) -> dict:
    n = max(len(ops), 1)
    busy = coll = exposed = 0.0
    per_op: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for dev, evs in ops.items():
        allv = union([(s, e) for _, s, e in evs])
        leaf = leaves(evs)
        cv = union([(s, e) for t, s, e in leaf if opcode(t) in COLLECTIVE])
        ov = union([(s, e) for t, s, e in leaf
                    if opcode(t) not in COLLECTIVE])
        busy += length(allv)
        coll += sum(e - s for t, s, e in leaf if opcode(t) in COLLECTIVE)
        exposed += length(subtract(cv, ov))
        for t, s, e in leaf:
            per_op[instruction(t)] += e - s
        off = clock_offset(mods.get(dev, []), done)
        for (_, e0), (s1, _) in zip(allv, allv[1:]):
            where = label((e0 + s1) / 2 + off, host, labels) \
                if off is not None else "unlabelled"
            gaps[where] += s1 - e0
    ns = 1e-9
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy / n * ns,
        "window_s": window_s,
        "devices": len(ops),
        "steps": steps,
        "collective_s": coll / n * ns,
        "exposed_collective_s": exposed / n * ns,
        "device_ops": [[k, v / n * ns] for k, v in top],
        "idle_gaps": [[k, v / n * ns] for k, v in idle],
    }


def summarize(trace_dir, *, steps: int, window_s: float) -> dict:
    import jax
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(str(paths[-1]))
    ops, mods, host, done = load(pd)
    return reduce(ops, mods, host, done, steps=steps, window_s=window_s)
