"""Observability: tracing, shared summaries, straggler forensics
(DESIGN.md §10).

- :mod:`repro.obs.trace` — the flight-recorder :class:`Tracer` (spans /
  instants / counters / structured events, Chrome-trace + JSONL export)
  and the zero-overhead :data:`NULL_TRACER` off-path.
- :mod:`repro.obs.stats` — the one streaming :class:`Summary` /
  :func:`pct` every percentile in the repo routes through.
- :mod:`repro.obs.straggler` — :class:`StragglerForensics`, the per-worker
  blame/drift ledger assembled live or from a JSONL log.
"""

from repro.obs.stats import Summary, pct
from repro.obs.straggler import StragglerForensics, WorkerLedger
from repro.obs.trace import NULL_TRACER, NullTracer, Tracer

__all__ = [
    "NULL_TRACER",
    "NullTracer",
    "StragglerForensics",
    "Summary",
    "Tracer",
    "WorkerLedger",
    "pct",
]
