"""Host intervals of the program's phase spans, for the per-layer readers.

The store opens a phase span, on its observer hook, for each step inside
a request or a background job (``read.memtable``, ``gc.lookup``,
``job.pick``, ...).  The store runs on one thread, so a span nested in
another is an interval inside that span's interval.  A program that opens
no phase span (only the request and job spans) gives these readers
nothing to read: they then report nothing.
"""

import bisect

# The program's phase names (``repro.obs.PHASES``), kept here so that the
# readers also run against a program that predates them.
PHASES = frozenset({
    "read.memtable", "memtable.snapshot", "read.tables", "read.values",
    "device.upload", "job.pick",
    "gc.read", "gc.lookup", "gc.value_read", "gc.write", "gc.finalize",
    "compact.merge", "compact.cut", "compact.install",
})
READS = ("multi_get", "multi_scan")


def has_phases(run) -> bool:
    return any(s[0] in PHASES for s in run.spans)


def intervals(run, names) -> list:
    """Sorted ``(t0, t1)`` of the spans named in ``names``."""
    return sorted((t0, t1) for name, _, t0, t1 in run.spans if name in names)


def inside(t0: float, t1: float, outer: list) -> bool:
    """``[t0, t1]`` lies within one of ``outer`` (sorted, disjoint)."""
    i = bisect.bisect_right(outer, (t0, float("inf"))) - 1
    return i >= 0 and outer[i][1] >= t1


def union(ivs) -> float:
    """Seconds covered by the union of ``(t0, t1)`` intervals."""
    busy, end = 0.0, None
    for t0, t1 in sorted(ivs):
        if end is None or t0 > end:
            busy += t1 - t0
            end = t1
        elif t1 > end:
            busy += t1 - end
            end = t1
    return busy


def in_reads(run, name) -> list:
    """``(t0, t1)`` of the ``name`` spans inside a read request's span
    and not inside GC-Lookup (whose lookups use the same read path)."""
    reads, lookups = intervals(run, READS), intervals(run, ("gc.lookup",))
    return [(t0, t1) for n, _, t0, t1 in run.spans
            if n == name and inside(t0, t1, reads)
            and not inside(t0, t1, lookups)]


def per_read_ms(run, name):
    """Wall of the ``name`` spans inside read requests, ms per read
    request; None where the program opens no phase span."""
    n = len(run.read_requests)
    if not n or not has_phases(run):
        return None
    return sum(t1 - t0 for t0, t1 in in_reads(run, name)) * 1e3 / n


def window_pct(run, names):
    """Union of the spans named in ``names`` over the window, in %."""
    if not has_phases(run):
        return None
    return 100.0 * union(intervals(run, names)) / (run.t1 - run.t0)


def wall_by_name(run, prefixes) -> dict:
    """Summed wall, s, of each span whose name starts with one of
    ``prefixes``."""
    out = {}
    for name, _, t0, t1 in run.spans:
        if name.startswith(prefixes):
            out[name] = out.get(name, 0.0) + (t1 - t0)
    return out
