"""Self wall of the write path per write request, ms: each ``write``
span less the union of the spans nested in it (the jobs it runs inline,
the pickers, their phases).  Moves ``write_p95_ms``."""

import bisect

from chipbench.phases import has_phases, union


def read(run):
    n = sum(1 for d in run.window if d.req.kind == "write")
    if not n or not has_phases(run):
        return None
    spans = sorted((t0, t1, name) for name, _, t0, t1 in run.spans)
    starts = [s[0] for s in spans]
    own = 0.0
    for i, (t0, t1, name) in enumerate(spans):
        if name != "write":
            continue
        hi = bisect.bisect_right(starts, t1)
        nested = [(a, b) for a, b, _ in spans[i + 1:hi] if b <= t1]
        own += (t1 - t0) - union(nested)
    return own * 1e3 / n
