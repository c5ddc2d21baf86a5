"""Host wall of the value fetches per read request, ms: the
``read.values`` spans inside ``multi_get`` spans (chain resolution,
per-vSST finds, run planning, cache and I/O accounting).  Moves
``read_p95_ms``."""

from chipbench.phases import per_read_ms


def read(run):
    return per_read_ms(run, "read.values")
