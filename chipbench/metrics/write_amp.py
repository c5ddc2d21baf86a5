"""Bytes written to the store's simulated device in the traced window
(``store.io`` counters, all categories) per user byte written (key and
value bytes of the window's writes).  A count, not a time.  Moves
``write_p95_ms``."""


def read(run):
    if run.user_write_bytes <= 0:
        return None
    return run.device_write_bytes / run.user_write_bytes
