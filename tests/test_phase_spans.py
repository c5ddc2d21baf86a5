"""Phase spans (DESIGN.md §11): the steps inside a request or a job,
opened on the observer hook and timed only by host-clock observers.

  * each phase span appears and nests inside the work it belongs to:
    ``read.*`` in ``multi_get`` or ``gc.lookup``, ``gc.*`` in ``gc``,
    ``compact.*`` in ``compact``, ``job.pick`` outside every job;
  * a ``multi_get`` opens the same spans however many files it probes;
  * the simulated-clock ``Observer`` records none of them.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from repro.core import EngineConfig, Store, WriteBatch
from repro.obs import PHASES, NullObserver, Observer

N_KEYS = 2048
VSIZES = np.array([64, 200, 600, 2000, 9000], np.int64)
JOBS = ("flush", "compact", "gc")
READS = ("read.memtable", "read.tables", "read.values")


class _Wall:
    __slots__ = ("obs", "name", "t0")

    def __init__(self, obs, name):
        self.obs, self.name = obs, name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.obs.spans.append((self.name, self.t0, time.perf_counter()))
        return False


class HostClock(NullObserver):
    """Every span as ``(name, t0, t1)`` on the host clock."""

    def __init__(self):
        self.spans: list[tuple] = []

    def span(self, store, name, lane="fg", **args):
        return _Wall(self, name)


def _drive(store, groups: int = 24, seed: int = 0) -> None:
    rng = np.random.default_rng(seed)
    for _ in range(groups):
        keys = rng.integers(0, N_KEYS, 256).astype(np.uint64)
        store.write(WriteBatch().puts(
            keys, VSIZES[rng.integers(0, len(VSIZES), 256)]))
        store.multi_get(rng.integers(0, N_KEYS, 192).astype(np.uint64))
    store.drain()
    store.multi_get(np.arange(N_KEYS, dtype=np.uint64))


def _parents(spans, child):
    """Names of the spans whose interval holds ``child``'s."""
    name, t0, t1 = child
    return {n for n, a, b in spans
            if (n, a, b) != child and a <= t0 and t1 <= b}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    obs = HostClock()
    cfg = EngineConfig.scaled("scavenger", 8 << 20, est_keys=N_KEYS,
                              observer=obs, kernel_min_batch=1)
    store = Store(cfg)
    _drive(store)
    assert store.n_compactions > 0 and store.n_gc_runs > 0
    # a restored memtable rebuilds its snapshot from the dict on its first
    # read; every other read merges the writes since the last one
    keys = np.arange(0, N_KEYS, 7, dtype=np.uint64)
    store.write(WriteBatch().puts(keys, np.full(len(keys), 600, np.int64)))
    path = store.checkpoint(tmp_path_factory.mktemp("snap") / "s.ckpt")
    Store.open(path, observer=obs).multi_get(
        np.arange(N_KEYS, dtype=np.uint64))
    return obs.spans


def test_every_phase_span_appears(traced):
    assert PHASES <= {n for n, _, _ in traced}


@pytest.mark.parametrize("prefix,parents", [
    ("read.memtable", {"multi_get", "gc.lookup"}),
    ("read.tables", {"multi_get", "gc.lookup"}),
    ("read.values", {"multi_get"}),
    ("memtable.snapshot", {"read.memtable"}),
    ("memtable.merge", {"read.memtable"}),
    ("gc.", {"gc"}),
    ("compact.", {"compact"}),
    ("device.upload", {"read.memtable", "read.tables", "read.values",
                       "gc.value_read"}),
])
def test_phase_span_nests_in_its_parent(traced, prefix, parents):
    spans = [s for s in traced if s[0].startswith(prefix)]
    assert spans
    for s in spans:
        assert _parents(traced, s) & parents, s


def test_job_pick_is_outside_every_job(traced):
    picks = [s for s in traced if s[0] == "job.pick"]
    assert picks
    for s in picks:
        assert not _parents(traced, s) & set(JOBS), s


def test_spans_per_multi_get_do_not_grow_with_files():
    """Each read phase opens once per ``multi_get``, whether the lookup
    probes one kSST or many."""
    obs = HostClock()
    cfg = EngineConfig.scaled("scavenger", 8 << 20, est_keys=N_KEYS,
                              observer=obs, kernel_min_batch=1,
                              l0_trigger=64, l0_slowdown=64, l0_stop=64)
    store = Store(cfg)
    counts = {}
    keys = np.arange(0, N_KEYS, 4, dtype=np.uint64)
    for i in range(6):
        store.write(WriteBatch().puts(keys + i % 4,
                                      np.full(len(keys), 600, np.int64)))
        store.flush()
        store.multi_get(keys)                   # uploads the new columns
        obs.spans.clear()
        store.multi_get(keys)
        get = [s for s in obs.spans if s[0] == "multi_get"]
        assert len(get) == 1
        counts[len(store.version.levels[0])] = sorted(
            n for n, a, b in obs.spans
            if n in PHASES and a >= get[0][1] and b <= get[0][2])
    assert len(counts) == 6 and max(counts) >= 6 * min(counts)
    assert all(c == sorted(READS) for c in counts.values()), counts


def test_simulated_observer_records_no_phase():
    obs = Observer(sample_every=16)
    cfg = EngineConfig.scaled("scavenger", 8 << 20, est_keys=N_KEYS,
                              observer=obs, kernel_min_batch=1)
    store = Store(cfg)
    _drive(store, groups=12)
    assert store.n_compactions > 0 and store.n_gc_runs > 0
    names = {ev["name"] for ev in obs.tracer.events}
    assert {"write", "multi_get", "flush", "compact", "gc"} <= names
    assert not names & PHASES
    assert not {f"{p}_us" for p in PHASES} & set(obs.metrics.names())
    for name in PHASES:
        assert obs.span(store, name) is NullObserver().span(store, name)
