"""Host wall inside kernel-routed calls (the sum of ``kernel_<op>_us``:
dispatch, transfer and kernel) per read request, ms.  Moves
``read_p95_ms``."""


def read(run):
    n = len(run.read_requests)
    if not n:
        return None
    return sum(us for _, us, _ in run.kernel_calls) / 1e3 / n
