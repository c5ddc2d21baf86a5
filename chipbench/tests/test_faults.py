"""The check fails a run whose timed path is broken underneath, in every
cell, for each fault a store can have on one chip: a write acknowledged
and not applied (the state left unchanged), half of each batch left out,
and an answer altered where it is produced (a read's vid, a write's
acknowledged vid).  It also fails the control, the reference with reads
one batch behind (``control.StaleReads``)."""

import io
import json
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness
from chipbench.control import stale_factory
from repro.core import Store

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _mint(store, n):
    vids = np.arange(store.next_vid, store.next_vid + n, dtype=np.uint64)
    store.next_vid += n
    return vids


class Unchanged(Store):
    def write(self, batch):
        return _mint(self, len(batch))


class HalfBatch(Store):
    def write(self, batch):
        kinds, keys, sizes = batch.arrays()
        h = len(keys) // 2
        return np.concatenate([self._write_arrays(kinds[:h], keys[:h],
                                                  sizes[:h]),
                               _mint(self, len(keys) - h)])


class AlteredRead(Store):
    def multi_get(self, keys):
        r = super().multi_get(keys)
        r["vid"] = r["vid"].copy()
        r["vid"][len(keys) // 2] ^= np.uint64(1)
        return r


class AlteredAck(Store):
    def write(self, batch):
        vids = super().write(batch).copy()
        vids[-1] += np.uint64(1 << 40)
        return vids


def _run(cell, factory):
    res = harness.run_cell(ROOT, cell, 2**31 + 99, 0.5, False,
                           time.perf_counter(), rehearsal=True,
                           store_factory=factory, out=io.StringIO(),
                           err=io.StringIO())
    return res


def _as(cls):
    def factory(cfg, observer=None):
        store = harness.make_store(cfg, observer)
        store.__class__ = cls
        return store
    return factory


@pytest.mark.parametrize("fault", [Unchanged, HalfBatch, AlteredRead,
                                   AlteredAck])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault):
    res = _run(cell, _as(fault))
    assert res["correct"] is False
    assert sum(v["value"] for v in res["compared"].values()) > 0


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell):
    res = _run(cell, stale_factory)
    assert res["correct"] is False
    wrong = res["compared"]
    assert wrong["read_wrong"]["value"] + wrong["readback_wrong"]["value"] \
        > 0
