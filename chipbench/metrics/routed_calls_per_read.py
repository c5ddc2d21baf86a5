"""Kernel-routed calls (``kernel_<op>_us`` events) per read request in
the traced window.  Moves ``read_p95_ms``."""


def read(run):
    n = len(run.read_requests)
    return len(run.kernel_calls) / n if n else None
