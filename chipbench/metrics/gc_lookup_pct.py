"""Share of the traced window inside GC-Lookup (the union of ``gc.lookup``
spans), in %.  The wall of every ``gc.*`` and ``compact.*`` phase goes to
standard error.  Moves ``write_p95_ms``."""

import sys

from chipbench.phases import wall_by_name, window_pct


def read(run):
    v = window_pct(run, ("gc.lookup",))
    if v is not None:
        walls = {k: round(s, 6) for k, s in
                 sorted(wall_by_name(run, ("gc.", "compact.")).items())}
        print(f"[chipbench] gc and compaction phase wall, s: {walls}",
              file=sys.stderr)
    return v
