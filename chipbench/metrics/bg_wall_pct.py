"""Share of the traced window's wall spent inside background jobs (the
``flush``, ``compact`` and ``gc`` spans of ``Store.run_job``), in %.  The
split by kind goes to standard error.  A window in which no job ran
reads 0.  Moves ``write_p95_ms``."""

import sys

JOBS = ("flush", "compact", "gc")


def read(run):
    by_kind = {k: 0.0 for k in JOBS}
    ivs = []
    for name, _, t0, t1 in run.spans:
        if name in JOBS:
            by_kind[name] += t1 - t0
            ivs.append((t0, t1))
    ivs.sort()
    busy, end = 0.0, None
    for t0, t1 in ivs:
        if end is None or t0 > end:
            busy += t1 - t0
            end = t1
        elif t1 > end:
            busy += t1 - end
            end = t1
    print(f"[chipbench] bg wall by kind, s: {by_kind}", file=sys.stderr)
    return 100.0 * busy / (run.t1 - run.t0)
