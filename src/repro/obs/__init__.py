"""Observability subsystem: tracing, metrics, and health on the simulated
two-lane clock (DESIGN.md §11).

Three composable pieces behind one ``Observer`` hook object:

  * ``trace``   — span tracer on the per-lane simulated clocks, ring-
                  buffered, exportable as Chrome trace-event JSON
  * ``metrics`` — counters / gauges / mergeable log-bucket histograms
                  (p50/p95/p99 per op class, per-engine/per-shard labels)
  * ``health``  — periodic derived snapshots (space amp, s_index, vSST
                  temperature mix, garbage distribution, lane utilization)

Two more pieces make causality first-class (DESIGN.md §13):

  * ``causality`` — deterministic span ids with parent/child links and
                    trace ids (request-scoped tracing)
  * ``ledger``    — the amplification attribution ledger: every SimIO
                    byte charged to a cause record, conserved
                    byte-identically against the per-category counters

Attach via ``EngineConfig(observer=Observer())``; the default
``NullObserver`` keeps observability-off runs byte-identical to
un-instrumented ones.  This package must stay import-free of
``repro.core`` (the core imports it) — I/O category names are plain
strings here for that reason.
"""

from .causality import Causality, Frame
from .health import HealthSampler, sample_store
from .ledger import (AmplificationLedger, blame_rows, cause_key,
                     check_conservation, live_breakdown, parse_cause)
from .metrics import Counter, Gauge, LogHist, MetricsRegistry
from .observer import NULL_OBSERVER, PHASES, NullObserver, Observer
from .trace import SpanTracer, chrome_trace, dump_chrome_trace

__all__ = ["AmplificationLedger", "Causality", "Counter", "Frame", "Gauge",
           "HealthSampler", "LogHist", "MetricsRegistry", "NULL_OBSERVER",
           "NullObserver", "Observer", "PHASES", "SpanTracer", "blame_rows",
           "cause_key", "check_conservation", "chrome_trace",
           "dump_chrome_trace", "live_breakdown", "parse_cause",
           "sample_store"]
