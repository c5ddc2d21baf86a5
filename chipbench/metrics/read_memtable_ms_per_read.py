"""Host wall of the memtable probes per read request, ms: the
``read.memtable`` spans inside ``multi_get``/``multi_scan`` spans (the
active and immutable memtables, routed or host, snapshot rebuilds
included).  The count and wall of the ``memtable.snapshot`` rebuilds go
to standard error.  Moves ``read_p95_ms``."""

import sys

from chipbench.phases import in_reads, per_read_ms


def read(run):
    v = per_read_ms(run, "read.memtable")
    if v is not None:
        snaps = in_reads(run, "memtable.snapshot")
        print(f"[chipbench] memtable snapshot rebuilds in reads: "
              f"{len(snaps)}, {sum(b - a for a, b in snaps):.6f} s",
              file=sys.stderr)
    return v
