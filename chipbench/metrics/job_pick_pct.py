"""Share of the traced window spent choosing background work (the union
of ``job.pick`` spans: the compaction and GC pickers), in %.  Moves
``write_p95_ms``."""

from chipbench.phases import window_pct


def read(run):
    return window_pct(run, ("job.pick",))
