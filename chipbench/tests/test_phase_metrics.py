"""The readers of the program's phase spans, on a hand-built window whose
spans give known values, and on a window of a program that opens no
phase span (they then report nothing)."""

from pathlib import Path

import numpy as np
import pytest

from chipbench.harness import Done, RunData, load_reader
from chipbench.phases import PHASES
from chipbench.traffic import Request
from repro.obs import PHASES as PROGRAM_PHASES

BENCH_DIR = Path(__file__).resolve().parents[1]
KEYS = np.arange(4, dtype=np.uint64)


def _req(kind):
    return Request(kind, kind, KEYS, np.full(4, 100, np.int64))


# t in seconds; the window is [0, 100)
SPANS = [
    # a read: 10 s, memtable 3 (snapshot 1 of it), tables 4, values 2
    ("multi_get", "fg", 0.0, 10.0),
    ("read.memtable", "fg", 0.5, 3.5),
    ("memtable.snapshot", "fg", 1.0, 2.0),
    ("device.upload", "fg", 2.5, 3.0),
    ("read.tables", "fg", 4.0, 8.0),
    ("read.values", "fg", 8.0, 10.0),
    ("job.pick", "bg", 10.0, 11.0),
    # a write of 30 s that runs a GC job inline: 20 s nested in all
    ("write", "fg", 20.0, 50.0),
    ("job.pick", "gc", 21.0, 23.0),
    ("gc", "gc", 24.0, 42.0),
    ("gc.read", "gc", 24.0, 25.0),
    ("gc.lookup", "gc", 25.0, 35.0),
    # GC-Lookup's own read path counts toward no read request
    ("read.memtable", "gc", 26.0, 30.0),
    ("read.tables", "gc", 30.0, 34.0),
    ("device.upload", "gc", 31.0, 32.0),
    ("gc.write", "gc", 35.0, 40.0),
    ("gc.finalize", "gc", 40.0, 42.0),
    # a second read: memtable 1, tables 1, nothing to fetch
    ("multi_get", "fg", 60.0, 70.0),
    ("read.memtable", "fg", 60.0, 61.0),
    ("read.tables", "fg", 61.0, 62.0),
    # a write that runs nothing inline
    ("write", "fg", 80.0, 82.0),
    ("job.pick", "bg", 81.0, 81.5),
]
WINDOW = [Done(_req("read"), 0.0, 10.0, None),
          Done(_req("write"), 20.0, 50.0, None),
          Done(_req("read"), 60.0, 70.0, None),
          Done(_req("write"), 80.0, 82.0, None)]


def _run(spans):
    return RunData(WINDOW, 0.0, 100.0, spans, [], 0, 0, None, None)


@pytest.mark.parametrize("metric,want", [
    ("read_memtable_ms_per_read", (3.0 + 1.0) * 1e3 / 2),
    ("read_tables_ms_per_read", (4.0 + 1.0) * 1e3 / 2),
    ("read_values_ms_per_read", 2.0 * 1e3 / 2),
    ("uploads_per_request", 2 / 4),
    ("gc_lookup_pct", 10.0),
    ("job_pick_pct", 1.0 + 2.0 + 0.5),
    # (30 - (2 + 18)) + (2 - 0.5), per write
    ("write_self_ms_per_write", (10.0 + 1.5) * 1e3 / 2),
])
def test_reader_gives_the_known_value(metric, want):
    assert load_reader(BENCH_DIR, metric)(_run(SPANS)) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "read_memtable_ms_per_read", "read_tables_ms_per_read",
    "read_values_ms_per_read", "uploads_per_request", "gc_lookup_pct",
    "job_pick_pct", "write_self_ms_per_write"])
def test_without_phase_spans_the_reader_reports_nothing(metric):
    only_requests = [s for s in SPANS
                     if s[0] in ("multi_get", "write", "gc")]
    assert load_reader(BENCH_DIR, metric)(_run(only_requests)) is None


def test_stderr_carries_the_split(capsys):
    run = _run(SPANS)
    load_reader(BENCH_DIR, "read_memtable_ms_per_read")(run)
    load_reader(BENCH_DIR, "uploads_per_request")(run)
    load_reader(BENCH_DIR, "gc_lookup_pct")(run)
    err = capsys.readouterr().err
    assert "memtable snapshot rebuilds in reads: 1, 1.000000 s" in err
    assert "device uploads: 2, 1.500000 s" in err
    assert "'gc.lookup': 10.0" in err and "'gc.write': 5.0" in err


def test_the_benchmark_knows_every_phase_the_program_opens():
    assert PHASES == PROGRAM_PHASES
