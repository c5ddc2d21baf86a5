"""Host wall of the read path per read request, ms: the ``multi_get`` and
``multi_scan`` spans less the routed-call wall that ends inside them.
Moves ``read_p95_ms``."""

import bisect

READ_SPANS = ("multi_get", "multi_scan")


def read(run):
    n = len(run.read_requests)
    spans = [(t0, t1) for name, _, t0, t1 in run.spans if name in READ_SPANS]
    if not n or not spans:
        return None
    calls = run.kernel_calls            # in the order they ended
    ends = [t for _, _, t in calls]
    routed = 0.0
    for t0, t1 in spans:
        lo, hi = bisect.bisect_left(ends, t0), bisect.bisect_right(ends, t1)
        routed += sum(us for _, us, _ in calls[lo:hi]) * 1e-6
    total = sum(t1 - t0 for t0, t1 in spans)
    return (total - routed) * 1e3 / n
