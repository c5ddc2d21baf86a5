"""Share of the traced window in which no op ran on the device, in %:
1 - (union of device op intervals) / window.  Moves ``ops_per_s``."""


def read(run):
    red = run.reduced
    if red is None or red.busy_s <= 0:
        return None
    return 100.0 * red.idle_share
