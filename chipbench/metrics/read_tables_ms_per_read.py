"""Host wall of the kSST probes per read request, ms: the ``read.tables``
spans inside ``multi_get``/``multi_scan`` spans (bloom hashing, L0 and
L1..Ln file assignment and probes, block accounting).  Moves
``read_p95_ms``."""

from chipbench.phases import per_read_ms


def read(run):
    return per_read_ms(run, "read.tables")
