"""The profiler trace of a traced run, and its reduction to numbers.

``start`` and ``stop_and_reduce`` bracket the traced window with
``jax.profiler``.  ``events_from_xplane`` flattens the written
``.xplane.pb`` to plain events ``[plane, line, name, start_ns, dur_ns]``,
keeping only what the reduction reads: the device planes' op and module
lines and the host's annotated spans.  ``reduce`` turns those events into:

  * busy time: the union of the intervals in which an op ran on a device,
    inside the window the host annotated ``chipbench:window``, averaged
    over the chips used; the idle share is 1 - busy / window;
  * kernel time by the name table ``kernel_names.json`` (device modules
    matched by name prefix), with the number of calls;
  * the device ops that took most time (by HLO op name, numbering
    stripped), and the longest idle gaps, each named by the innermost host
    span open at the gap's middle: ``store:<hook>`` from the benchmark's
    observer, ``client:<kind>`` around each request, else ``client:loop``.

The profiler runs with its Python tracer off, so that tracing costs the
host little; the host spans come from ``jax.profiler.TraceAnnotation``.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import re
import shutil
from pathlib import Path

WINDOW_SPAN = "chipbench:window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_SPAN_PREFIXES = ("chipbench:", "store:", "client:")
TOP = 10


def start(bench_dir: Path) -> Path:
    import jax
    d = bench_dir / ".trace"
    shutil.rmtree(d, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)
    return d


def stop_and_reduce(d: Path, bench_dir: Path, chips: int = 1):
    import jax
    jax.profiler.stop_trace()
    paths = glob.glob(str(d / "plugins" / "profile" / "*" / "*.xplane.pb"))
    if not paths:
        return None
    events = events_from_xplane(paths[0])
    shutil.rmtree(d, ignore_errors=True)
    return reduce(events, load_name_table(bench_dir), chips)


def op_name(hlo: str) -> str:
    """``%rank_probe_pallas.1 = (s32[256,1]...) custom-call(...)`` ->
    ``rank_probe_pallas``."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def events_from_xplane(path: str) -> list:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for ln in plane.lines:
                if ln.name in (OPS_LINE, MODULES_LINE):
                    out += [[plane.name, ln.name,
                             op_name(e.name) if ln.name == OPS_LINE
                             else e.name, float(e.start_ns),
                             float(e.duration_ns)] for e in ln.events]
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                out += [[plane.name, ln.name, e.name, float(e.start_ns),
                         float(e.duration_ns)] for e in ln.events
                        if e.name.startswith(HOST_SPAN_PREFIXES)]
    return out


def load_name_table(bench_dir: Path) -> dict:
    return json.loads((bench_dir / "kernel_names.json").read_text())


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    kernels: dict           # group -> {"seconds", "calls"}
    device_ops: list        # [[name, seconds]] most time first
    idle_gaps: list         # [[host span, seconds]] longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops, "idle_gaps": self.idle_gaps}


def reduce(events: list, names: dict, chips: int = 1) -> Reduced | None:
    host = [e for e in events if not e[0].startswith(DEVICE_PREFIX)]
    win = [e for e in host if e[2] == WINDOW_SPAN]
    if not win:
        return None
    w0, w1 = win[0][3], win[0][3] + win[0][4]
    planes = sorted({e[0] for e in events if e[0].startswith(DEVICE_PREFIX)}
                    )[:chips]
    busy_ns, gaps, op_time, kernels = 0.0, [], {}, {}
    for p in planes:
        ops = [e for e in events if e[0] == p and e[1] == OPS_LINE]
        if not ops:
            ops = [e for e in events if e[0] == p and e[1] == MODULES_LINE]
        clipped = [(max(e[3], w0), min(e[3] + e[4], w1)) for e in ops
                   if e[3] < w1 and e[3] + e[4] > w0]
        merged = _union(clipped)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for s, e, ev in ((max(e[3], w0), min(e[3] + e[4], w1), e)
                         for e in ops if e[3] < w1 and e[3] + e[4] > w0):
            op_time[ev[2]] = op_time.get(ev[2], 0.0) + (e - s)
        for ev in events:
            if ev[0] != p or ev[1] != MODULES_LINE or not (w0 <= ev[3] < w1):
                continue
            for group, prefixes in names.items():
                if any(ev[2].startswith(pre) for pre in prefixes):
                    k = kernels.setdefault(group, {"seconds": 0.0,
                                                   "calls": 0})
                    k["seconds"] += ev[4] * 1e-9
                    k["calls"] += 1
    n = max(len(planes), 1)
    spans = [e for e in host if e[2] != WINDOW_SPAN]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = (s + e) / 2
        inner = [h for h in spans if h[3] <= mid < h[3] + h[4]]
        label = (max(inner, key=lambda h: h[3])[2] if inner
                 else "client:loop")
        named.append([label, (e - s) * 1e-9])
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9 / n,
                   kernels=kernels,
                   device_ops=[[k, v * 1e-9 / n] for k, v in ops_top],
                   idle_gaps=named)
