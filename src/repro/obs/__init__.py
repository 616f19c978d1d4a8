"""Observability plane — host side.

The always-on profiler policies (``repro.policies.profiler``) stream
straggler events into a ringbuf map and bucket latencies into a
per-device histogram; this package is the consumer half:

* :class:`FlightRecorder` — drains the event ring into a bounded
  host-side record store (itself a ringbuf, overwrite mode) and
  snapshots the histogram; exposes drop/overflow counters and a
  ``health()`` dict the runtime/dispatcher health surfaces merge.
* :class:`Exporter` — serializes recorder snapshots as JSON-lines
  (histogram / straggler / counters records) for offline tooling.
* :mod:`~repro.obs.spans` — the program's host spans and counters
  (trainer steps, data pipeline, link replacement), always on, bounded,
  and annotated on any profile taken while they run.
"""

from . import spans
from .exporter import Exporter
from .recorder import FlightRecorder, StragglerRecord, bucket_lower_bounds
from .spans import SpanStore

__all__ = ["FlightRecorder", "StragglerRecord", "Exporter",
           "bucket_lower_bounds", "spans", "SpanStore"]
