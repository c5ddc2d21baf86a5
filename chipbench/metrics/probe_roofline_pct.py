"""Share of the probe kernels' roofline: the least time the chip could
take for the calls the trace shows, over their device time, in %.

The least time is bound by bytes: ``work.least_bytes`` per call over the
chip's HBM bandwidth (``work/peaks.json``).  A share over 100% means the
byte count is too high or the time misses part of the call.  Moves
``read_p95_ms``."""

from chipbench.work import least_bytes

KINDS = {"probe.lookup": "lookup", "probe.rank": "rank",
         "probe.count_le": "count_le"}


def read(run):
    red = run.reduced
    if red is None or run.peaks is None:
        return None
    calls = {g: v for g, v in red.kernels.items()
             if g in KINDS and v["calls"]}
    seconds = sum(v["seconds"] for v in calls.values())
    if seconds <= 0:
        return None
    least = sum(least_bytes(KINDS[g]) * v["calls"] for g, v in calls.items())
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / seconds
