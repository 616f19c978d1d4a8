"""Device time of a traced window by the program's name scopes.

``jax.named_scope`` puts a scope into each HLO instruction's ``op_name``
metadata, and the profiler writes that path, as ``tf_op``, into the stats
of the instruction's event metadata on each device plane of the
``*.xplane.pb`` (``jit(step)/.../transpose(jvp(repro.mla))/dot_general``).
``jax.profiler.ProfileData`` does not expose event metadata, so this
module reads the file's protobuf wire format itself (the ``XSpace``
messages of ``tsl/profiler/protobuf/xplane.proto``).

Per device plane, the ``XLA Ops`` events that contain no other event (a
``while`` is one event around its body's) are summed by scope: an op
belongs to the last of the given scopes that its ``tf_op`` names (the
innermost), and to none when it names none.  XLA's rewrite of
``lax.ragged_dot`` into its TPU kernel replaces the ``op_name`` with its
own (``ragged-dot-none``, ``ragged-dot-metadata``): such ops are given to
the scope of :data:`RAGGED`, the only scope whose code calls
``ragged_dot``.  Times are averaged over the device planes.
"""

from __future__ import annotations

import struct
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Sequence, Tuple

RAGGED = ("ragged-dot", "repro.moe.experts")


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of a protobuf message: ints for varints,
    bytes for length-delimited fields, raw 8 or 4 bytes for fixed ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not handled")
        yield num, v


def _stat(b: bytes, names: Dict[int, str]) -> Tuple[str, object]:
    """An XStat as (stat name, value): a string for a string or a
    reference (to the stat metadata that holds the string), else a
    number."""
    mid, val = 0, None
    for num, v in fields(b):
        if num == 1:
            mid = v
        elif num == 5:
            val = v.decode("utf-8", "replace")
        elif num == 7:
            val = names.get(v, "")
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num in (3, 4):
            val = v
    return names.get(mid, ""), val


def device_ops(data: bytes) -> Dict[str, List[Tuple[str, int, int]]]:
    """Per device plane, its ``XLA Ops`` events ``(tf_op or op name, start
    ps, end ps)`` relative to the line's timestamp."""
    out = {}
    for num, plane in fields(data):
        if num != 1:
            continue
        name, lines, meta_raw, stat_raw = "", [], [], []
        for f, v in fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 3:
                lines.append(v)
            elif f == 4:
                meta_raw.append(v)
            elif f == 5:
                stat_raw.append(v)
        if not name.startswith("/device:") or \
                name.startswith("/device:CUSTOM"):
            continue
        stat_names = {}
        for entry in stat_raw:
            kv = dict(fields(entry))
            sm = dict(fields(kv.get(2, b"")))
            stat_names[kv.get(1, 0)] = sm.get(2, b"").decode()
        meta = {}
        for entry in meta_raw:
            kv = dict(fields(entry))
            em = kv.get(2, b"")
            mname, tf_op = "", None
            for f, v in fields(em):
                if f == 2:
                    mname = v.decode("utf-8", "replace")
                elif f == 5:
                    k, val = _stat(v, stat_names)
                    if k == "tf_op":
                        tf_op = val
            meta[kv.get(1, 0)] = tf_op or mname
        for ln in lines:
            lf = list(fields(ln))
            if dict((f, v) for f, v in lf if f == 2).get(2, b"") != \
                    b"XLA Ops":
                continue
            evs = []
            for f, ev in lf:
                if f != 4:
                    continue
                e = dict(fields(ev))
                start = e.get(2, 0)
                evs.append((meta.get(e.get(1, 0), ""), start,
                            start + e.get(3, 0)))
            out[name] = evs
    return out


def leaves(evs: list) -> list:
    """The events that contain no other."""
    evs = sorted(evs, key=lambda x: (x[1], -x[2]))
    return [ev for i, ev in enumerate(evs)
            if not (i + 1 < len(evs) and evs[i + 1][1] < ev[2]
                    and evs[i + 1][2] <= ev[2])]


def scope_of(tf_op: str, scopes: Sequence[str]) -> str:
    """The innermost of ``scopes`` that ``tf_op`` names, else ''."""
    if tf_op.startswith(RAGGED[0]):
        return RAGGED[1] if RAGGED[1] in scopes else ""
    best, at = "", -1
    for s in scopes:
        j = tf_op.rfind(s)
        if j > at:
            best, at = s, j
    return best


def reduce(ops: Dict[str, list], scopes: Sequence[str]) -> dict:
    """``{"seconds": {scope: s}, "leaf_s": s, "unattributed_s": s}``,
    averaged over the devices."""
    n = max(len(ops), 1)
    by = defaultdict(float)
    total = 0.0
    for evs in ops.values():
        for t, s, e in leaves(evs):
            total += e - s
            by[scope_of(t, scopes)] += e - s
    ps = 1e-12 / n
    return {"seconds": {s: by[s] * ps for s in scopes},
            "leaf_s": total * ps, "unattributed_s": by[""] * ps}


def summarize(trace_dir, scopes: Sequence[str]) -> dict:
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane under {trace_dir}")
    return reduce(device_ops(paths[-1].read_bytes()), scopes)
