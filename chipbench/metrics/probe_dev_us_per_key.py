"""Device time of the probe kernels per key read, in the traced window.

The probe kernels are found in the device trace by the name table
``kernel_names.json`` (groups ``probe.*``): the Pallas kernels and their
XLA fallbacks.  Moves ``read_p95_ms``."""


def read(run):
    red = run.reduced
    if red is None or run.read_keys == 0:
        return None
    s = sum(v["seconds"] for g, v in red.kernels.items()
            if g.startswith("probe."))
    return s * 1e6 / run.read_keys if s > 0 else None
