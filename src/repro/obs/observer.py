"""Observer hook surface wired through the core (DESIGN.md §11, §13).

``Store``/``ShardedStore``/``FleetScheduler``/``ServeEngine`` call one
hook object — ``EngineConfig.observer`` — at every instrumentation point.
The default ``NullObserver`` makes each hook a constant-time no-op that
never reads or writes the simulated device, so observability-off runs are
byte-identical to un-instrumented ones (golden-locked in
``tests/test_obs.py``).

``Observer`` is the real implementation: spans/instants go to a
``SpanTracer`` on the simulated lane clocks, scalar observations to a
``MetricsRegistry`` (per-engine/per-shard labels), periodic derived
snapshots to a ``HealthSampler``, and — §13 — every span doubles as a
*cause frame*: spans carry parent/child links and a trace id
(``causality.py``), and byte deltas between frame boundaries are charged
to the innermost cause in the ``AmplificationLedger`` (``ledger.py``),
which conserves byte-identically against the SimIO counters.

No-op contract (enforced by the ``obs-purity`` scavlint pass): hook code
may *read* store and SimIO state freely but must never advance a lane
clock, charge simulated I/O, or mutate store-rooted state — observability
is a tap, not a participant.
"""

from __future__ import annotations

import contextlib
import os

from .causality import USER_OPS, Causality
from .health import HealthSampler
from .ledger import AmplificationLedger
from .metrics import MetricsRegistry
from .trace import DEFAULT_CAP, SpanTracer, dump_chrome_trace

_NULL_CTX = contextlib.nullcontext()

# Phase spans: the steps inside a foreground op or a background job, one
# span per phase per call.  They are timed only by observers that keep a
# host clock; ``Observer`` hands back the null context for them, so they
# never become simulated-lane spans, cause frames or ledger scopes.
PHASES = frozenset({
    "read.memtable", "memtable.snapshot", "memtable.merge",
    "read.tables", "read.values", "device.upload", "job.pick",
    "gc.read", "gc.lookup", "gc.value_read", "gc.write", "gc.finalize",
    "compact.merge", "compact.cut", "compact.install",
})

# Byte/op counter fields snapshotted around a span to attach per-category
# payload deltas (names mirror SimIO's counters).
_IO_FIELDS = ("read_bytes", "write_bytes", "read_ops", "write_ops")


class NullObserver:
    """No-op observer: the default.  Every hook returns immediately; the
    span and cause hooks hand back one shared, reusable null context."""

    enabled = False

    def register_store(self, store) -> str:
        return "0"

    def span(self, store, name, lane="fg", **args):
        return _NULL_CTX

    def instant(self, store, name, lane="fg", **args) -> None:
        pass

    def lane_sync(self, store, lane, t0) -> None:
        pass

    def cause(self, store, **fields):
        return _NULL_CTX

    def on_op(self, store, name, value) -> None:
        pass

    def on_count(self, store, name, n=1) -> None:
        pass

    def on_stall(self, store, us, kind) -> None:
        pass

    def on_space(self, store, event, nbytes) -> None:
        pass

    def on_edit(self, store, kind, nbytes) -> None:
        pass

    def tick(self, store) -> None:
        pass


NULL_OBSERVER = NullObserver()


class _Span:
    """Context manager recording one span against a lane clock.

    ``dur`` is the *lane-time* delta, so nested work on other lanes (a
    ``pump()`` inside a foreground op) never pollutes this track — the
    per-(shard, lane) tiling invariant (see ``trace.py``) depends on it.
    On enter the span also becomes a causality frame (id/parent/trace) and
    a ledger cause scope (§13)."""

    __slots__ = ("obs", "store", "name", "lane", "args", "t0", "io0",
                 "frame")

    def __init__(self, obs, store, name, lane, args):
        self.obs = obs
        self.store = store
        self.name = name
        self.lane = lane
        self.args = args

    def __enter__(self):
        io = self.store.io
        self.t0 = io.lanes[self.lane]
        self.io0 = {f: dict(getattr(io, f)) for f in _IO_FIELDS}
        self.frame = self.obs._begin_span(self.store, self.name, self.lane,
                                          self.args)
        return self

    def __exit__(self, *exc):
        io = self.store.io
        t1 = io.lanes[self.lane]
        args = dict(self.args) if self.args else {}
        for f in _IO_FIELDS:
            before = self.io0[f]
            d = {k: v - before.get(k, 0)
                 for k, v in getattr(io, f).items() if v != before.get(k, 0)}
            if d:
                args[f] = d
        self.obs._end_span(self.store, self.name, self.lane, self.t0,
                           t1 - self.t0, args or None, self.frame)
        return False


class _Cause:
    """Ledger-only cause scope (no span event): fine-grained attribution
    inside a job frame — e.g. per-temperature vSST builds (§13)."""

    __slots__ = ("obs", "store", "fields", "token")

    def __init__(self, obs, store, fields):
        self.obs = obs
        self.store = store
        self.fields = fields

    def __enter__(self):
        self.token = self.obs.ledger.push(
            self.obs._label(self.store), self.store.io, self.fields,
            pin="origin" in self.fields)
        return self

    def __exit__(self, *exc):
        self.obs.ledger.pop(self.obs._label(self.store), self.store.io,
                            self.token)
        return False


class Observer(NullObserver):
    """Tracing + metrics + health + causal ledger, on the simulated
    clocks."""

    enabled = True

    def __init__(self, cap: int = DEFAULT_CAP, sample_every: int = 64,
                 health: HealthSampler | None = None):
        self.tracer = SpanTracer(cap=cap)
        self.metrics = MetricsRegistry()
        self.health = health or HealthSampler(sample_every=sample_every)
        self.ledger = AmplificationLedger()
        self.causality = Causality()
        self._stores: dict[str, object] = {}

    # ------------------------------------------------------------- registry
    def register_store(self, store) -> str:
        label = str(len(self._stores))
        self._stores[label] = store
        self.tracer.shard_meta[label] = {"engine": store.cfg.engine}
        self.ledger.register(label, store.io)
        return label

    def _label(self, store) -> str:
        return getattr(store, "obs_label", "0")

    def _labels(self, store) -> dict:
        return {"engine": store.cfg.engine, "shard": self._label(store)}

    # ---------------------------------------------------------------- spans
    def span(self, store, name, lane="fg", **args):
        if name in PHASES:
            return _NULL_CTX
        return _Span(self, store, name, lane, args)

    def _begin_span(self, store, name, lane, args):
        frame = self.causality.push()
        self.causality.note_user_op(name)
        overrides = {"op": name}
        cause = args.get("cause") if args else None
        if cause:
            overrides.update(cause)
        if name in USER_OPS:
            overrides.setdefault("trigger", "user")
        frame.label = self._label(store)
        frame.token = self.ledger.push(frame.label, store.io, overrides,
                                       global_origin=self.causality.origin)
        return frame

    def _end_span(self, store, name, lane, ts, dur, args, frame) -> None:
        self.ledger.pop(frame.label, store.io, frame.token)
        self.causality.pop(frame)
        self.tracer.span(name, lane, self._label(store), ts, dur, args,
                         span_id=frame.span_id, parent_id=frame.parent_id,
                         trace_id=frame.trace_id)
        self.metrics.hist(f"{name}_us", **self._labels(store)).record(
            dur, exemplar=frame.trace_id)

    def instant(self, store, name, lane="fg", **args) -> None:
        self.tracer.instant(name, lane, self._label(store),
                            store.io.lanes[lane], args or None,
                            trace_id=self.causality.current_trace())

    def lane_sync(self, store, lane, t0) -> None:
        """A scheduler jumped ``lane``'s clock from ``t0`` to its current
        value (stall service / drain barrier); record the jump as a span so
        the track still tiles the lane clock."""
        t1 = store.io.lanes[lane]
        if t1 > t0:
            self.tracer.span("lane_sync", lane, self._label(store), t0,
                             t1 - t0,
                             trace_id=self.causality.current_trace())

    # ---------------------------------------------------------- cause scopes
    def cause(self, store, **fields):
        return _Cause(self, store, fields)

    # -------------------------------------------------------------- metrics
    def on_op(self, store, name, value) -> None:
        self.metrics.hist(name, **self._labels(store)).record(
            value, exemplar=self.causality.current_trace() or None)

    def on_count(self, store, name, n=1) -> None:
        self.metrics.counter(name, **self._labels(store)).inc(n)

    def on_stall(self, store, us, kind) -> None:
        if us > 0:
            labels = self._labels(store)
            self.metrics.hist("stall_us", **labels).record(
                us, exemplar=self.causality.current_trace() or None)
            self.metrics.counter("stalls", kind=kind, **labels).inc()

    # --------------------------------------------------------------- ledger
    def on_space(self, store, event, nbytes) -> None:
        self.ledger.charge_space(self._label(store), event, nbytes)

    def on_edit(self, store, kind, nbytes) -> None:
        self.ledger.charge_edit(self._label(store), kind, nbytes)

    # --------------------------------------------------------------- health
    def tick(self, store) -> None:
        self.health.tick(store, self._label(store))

    # ------------------------------------------------------------ reporting
    def finish(self) -> None:
        """Record final per-shard lane clocks (the tiling reference), the
        final SimIO counter snapshots (the ledger conservation reference),
        and a last health sample for every registered store."""
        for label, store in self._stores.items():
            self.tracer.shard_lanes[label] = dict(store.io.lanes)
            self.ledger.finish(label, store.io, meta={
                "engine": store.cfg.engine,
                "user_write_bytes": store.user_write_bytes,
                "valid_bytes": store.valid_bytes,
                "space_bytes": store.space_bytes(),
            })
            self.health.sample(store, label)

    def dump(self, outdir, chrome: bool = True) -> dict:
        """Write events.json / metrics.json / health.json / ledger.json
        (and trace.json, the Chrome trace-event conversion) under
        ``outdir``."""
        self.finish()
        os.makedirs(outdir, exist_ok=True)
        paths = {}
        paths["events"] = os.path.join(outdir, "events.json")
        self.tracer.dump_json(paths["events"])
        paths["metrics"] = os.path.join(outdir, "metrics.json")
        self.metrics.dump_json(paths["metrics"])
        paths["health"] = os.path.join(outdir, "health.json")
        self.health.dump_json(paths["health"])
        paths["ledger"] = os.path.join(outdir, "ledger.json")
        self.ledger.dump_json(paths["ledger"])
        if chrome:
            paths["trace"] = os.path.join(outdir, "trace.json")
            dump_chrome_trace(self.tracer, paths["trace"])
        return paths
