"""Structure columns handed to the device (``device.upload`` spans, one
per device-cache miss of a routed call) per request of the traced window,
reads, writes and the background jobs they run alike.  Their wall goes to
standard error.  Moves ``ops_per_s``."""

import sys

from chipbench.phases import has_phases, intervals


def read(run):
    if not run.window or not has_phases(run):
        return None
    ups = intervals(run, ("device.upload",))
    print(f"[chipbench] device uploads: {len(ups)}, "
          f"{sum(b - a for a, b in ups):.6f} s", file=sys.stderr)
    return len(ups) / len(run.window)
