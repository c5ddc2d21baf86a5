"""The benchmark's own observer: host wall-clock stamps at the program's
existing observer hooks.

``Store`` calls ``EngineConfig.observer`` at ``write``, ``multi_get``,
``multi_scan`` and each ``run_job`` kind (``span``), and reports the host
wall of every kernel-routed op as ``kernel_<op>_us`` (``on_op``).  The
program's own ``Observer`` times spans on the simulated lane clocks; this
one stamps ``time.perf_counter`` instead, and wraps each span in a
``jax.profiler.TraceAnnotation`` so that the profiler's host track shows
what the host was doing while the device sat idle.  It reads nothing of
the store and changes nothing: every other hook stays the no-op of
``NullObserver``.  It records only while ``active`` (the traced window).
"""

from __future__ import annotations

import time

from repro.obs import NullObserver

KERNEL_PREFIX, KERNEL_SUFFIX = "kernel_", "_us"


class _WallSpan:
    __slots__ = ("obs", "name", "lane", "t0", "ann")

    def __init__(self, obs, name, lane):
        self.obs, self.name, self.lane = obs, name, lane

    def __enter__(self):
        from jax.profiler import TraceAnnotation
        self.ann = TraceAnnotation(f"store:{self.name}")
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.obs.spans.append((self.name, self.lane, self.t0, t1))
        return False


class WallObserver(NullObserver):
    """Spans as ``(name, lane, t0, t1)`` and routed ops as
    ``(op, wall_us, t_end)``, host clock, while ``active``."""

    def __init__(self):
        self.active = False
        self.spans: list[tuple] = []
        self.kernel_calls: list[tuple] = []

    def span(self, store, name, lane="fg", **args):
        if not self.active:
            return super().span(store, name, lane)
        return _WallSpan(self, name, lane)

    def on_op(self, store, name, value) -> None:
        if (self.active and name.startswith(KERNEL_PREFIX)
                and name.endswith(KERNEL_SUFFIX)):
            op = name[len(KERNEL_PREFIX):-len(KERNEL_SUFFIX)]
            self.kernel_calls.append((op, float(value), time.perf_counter()))
