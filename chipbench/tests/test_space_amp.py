"""``space_amp`` is the byte-weighted mean of stored over live bytes across
a fixed volume of user writes after set-up, so the client's speed does not
move it, a store that defers GC still reads higher, and a window that
writes less than the volume is followed by untimed requests that are
checked, read back, and counted in no timed metric."""

import io
import re
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness
from chipbench.traffic import Request
from repro.core import Store

ROOT = Path(__file__).resolve().parents[2]
CELL = "update.mixed8k"     # its volume spans about 110 rehearsal requests
SEED = 2**31 + 4321
OPS = 256


def _run(factory=None, seconds=0.5):
    err = io.StringIO()
    res = harness.run_cell(ROOT, CELL, SEED, seconds, False,
                           time.perf_counter(), rehearsal=True,
                           store_factory=factory, out=io.StringIO(), err=err)
    log = err.getvalue()
    n_warm = int(re.search(r"warm-up [\d.]+ s \((\d+) requests", log)[1])
    untimed = int(re.search(r"after the window (\d+) untimed", log)[1])
    return res, n_warm + res["attempted"], untimed


def _as(cls):
    def factory(cfg, observer=None):
        store = harness.make_store(cfg, observer)
        store.__class__ = cls
        return store
    return factory


class Slow(Store):
    def write(self, batch):
        time.sleep(0.1)
        return super().write(batch)

    def multi_get(self, keys):
        time.sleep(0.1)
        return super().multi_get(keys)


class DeferredGC(Store):
    """Runs no GC from the first request after set-up on."""

    def write(self, batch):
        if len(batch) <= OPS:
            self.deferring = True
        return super().write(batch)

    def next_gc_job(self):
        if getattr(self, "deferring", False):
            return None
        return super().next_gc_job()


@pytest.fixture(scope="module")
def fast():
    return _run()


def test_client_speed_does_not_move_it(fast):
    res, timed, _ = fast
    slow, slow_timed, slow_untimed = _run(_as(Slow))
    assert res["correct"] and slow["correct"]
    assert timed >= 2 * slow_timed and slow_untimed > 0
    assert slow["metrics"]["space_amp"] == res["metrics"]["space_amp"]


def test_deferred_gc_reads_higher(fast):
    res, _, _ = fast
    deferred, _, _ = _run(_as(DeferredGC))
    assert deferred["correct"]
    assert (deferred["metrics"]["space_amp"]["value"]
            > 1.5 * res["metrics"]["space_amp"]["value"])


class PostWindow(Store):
    """The second request sleeps; the 50th is acknowledged and not
    applied.  With one warm-up request and a window of one, both faults
    fall after the window."""

    def write(self, batch):
        if len(batch) > OPS:
            return super().write(batch)
        self.n = getattr(self, "n", 0) + 1
        if self.n == 2:
            time.sleep(0.3)
        if self.n == 50:
            vids = np.arange(self.next_vid, self.next_vid + len(batch),
                             dtype=np.uint64)
            self.next_vid += len(batch)
            return vids
        return super().write(batch)


def test_requests_after_the_window_are_checked_and_untimed(monkeypatch):
    monkeypatch.setattr(harness, "WARM_MIN", 1)
    monkeypatch.setattr(harness, "WARM_QUIET_S", 0.0)
    res, timed, untimed = _run(_as(PostWindow), seconds=0.1)
    assert timed == 2 and res["attempted"] == 1 and untimed >= 50
    assert res["correct"] is False and res["failed"] == 0
    assert res["compared"]["readback_wrong"]["value"] > 0
    m = res["metrics"]
    assert m["write_p95_ms"]["value"] >= 300
    assert m["ops_per_s"]["value"] <= OPS / 0.3


def test_byte_weighted_mean():
    def write(n, size):
        return harness.Done(Request("write", "update",
                                    np.zeros(n, np.uint64),
                                    np.full(n, size, np.int64)), 0, 0, None)
    read = harness.Done(Request("read", "read", np.zeros(4, np.uint64)),
                        0, 0, None)
    done = [write(2, 76), read, write(2, 76), write(2, 76)]
    # 200 B per write: 200 at 1.0, the read weighs nothing, 200 at 3.0,
    # then 100 of the last at 4.0 (cut at 500)
    ratios = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert harness.byte_weighted(ratios, done, 24, 500) == pytest.approx(
        (200 * 1.0 + 200 * 3.0 + 100 * 4.0) / 500)
