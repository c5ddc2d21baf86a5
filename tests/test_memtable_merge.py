"""Memtable snapshot as base + pending delta (DESIGN.md §2).

Writes append delta columns; ``snapshot()`` merges them into the cached
key-sorted columns.  The merged snapshot must equal a rebuild from the
dict bit for bit, must never write into an array it handed out, and keeps
its key column object when no key is new.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import EngineConfig, Store, WriteBatch
from repro.core.durability import snapshot as dsnap
from repro.core.engine.memtable import COLUMN_DTYPES, Memtable
from repro.core.engine.tables import ETYPE_INLINE, ETYPE_REF, ETYPE_TOMB


def _rebuild(mt: Memtable) -> tuple:
    """The snapshot a memtable with only ``entries`` would build."""
    fresh = Memtable(mt.cfg)
    fresh.entries = dict(mt.entries)
    fresh.drop_snapshot()
    return fresh.snapshot()


def _assert_same(got: tuple, want: tuple) -> None:
    assert len(got) == len(want) == len(COLUMN_DTYPES)
    for g, w, dt in zip(got, want, COLUMN_DTYPES):
        assert g.dtype == w.dtype == dt
        assert g.tobytes() == w.tobytes()


def _batch(rng, n: int, key_space: int) -> tuple:
    keys = rng.integers(0, key_space, n).astype(np.uint64)
    seqs = rng.integers(1, 1 << 40, n).astype(np.uint64)
    ety = rng.choice([ETYPE_INLINE, ETYPE_REF, ETYPE_TOMB], n).astype(
        np.uint8)
    vids = rng.integers(0, 1 << 50, n).astype(np.uint64)
    vsz = np.where(ety == ETYPE_TOMB, 0,
                   rng.integers(1, 3000, n)).astype(np.int64)
    vf = np.where(ety == ETYPE_REF, rng.integers(0, 99, n), -1).astype(
        np.int64)
    return keys, seqs, ety, vids, vsz, vf


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_merged_snapshot_equals_rebuild(seed):
    """Duplicate keys within and across batches, early stops at capacity,
    scalar writes, and reads between them: after every step the snapshot
    equals a rebuild from ``entries``."""
    rng = np.random.default_rng(seed)
    cfg = EngineConfig(engine="scavenger", memtable_bytes=200_000)
    mt = Memtable(cfg)
    stops = 0
    for step in range(60):
        r = rng.random()
        if r < 0.5:
            cols = _batch(rng, int(rng.integers(1, 300)), 400)
            used = mt.put_batch(*cols)
            stops += used < len(cols[0])
            if mt.full:
                mt = Memtable(cfg)
        elif r < 0.8:
            k = int(rng.integers(0, 400))
            op = int(rng.integers(0, 3))
            if op == 0:
                mt.put(k, step, step + 7, int(rng.integers(1, 3000)))
            elif op == 1:
                mt.put_ref(k, step, step + 7, int(rng.integers(1, 3000)), 5)
            else:
                mt.delete(k, step)
        if rng.random() < 0.6:
            _assert_same(mt.snapshot(), _rebuild(mt))
            assert mt.snapshot_current
    _assert_same(mt.snapshot(), _rebuild(mt))
    assert stops > 0


def test_last_write_wins_within_a_batch():
    mt = Memtable(EngineConfig(engine="scavenger"))
    keys = np.array([7, 3, 7, 7, 3], np.uint64)
    seqs = np.arange(1, 6, dtype=np.uint64)
    mt.put_batch(keys, seqs, np.full(5, ETYPE_INLINE, np.uint8),
                 seqs * 10, np.full(5, 100, np.int64),
                 np.full(5, -1, np.int64))
    k, s, _, v, _, _ = mt.snapshot()
    assert k.tolist() == [3, 7] and s.tolist() == [5, 4]
    assert v.tolist() == [50, 40]


def test_handed_out_arrays_are_never_written():
    rng = np.random.default_rng(5)
    mt = Memtable(EngineConfig(engine="scavenger"))
    mt.put_batch(*_batch(rng, 256, 500))
    held = mt.snapshot()
    before = [a.tobytes() for a in held]
    for _ in range(8):
        mt.put_batch(*_batch(rng, 256, 500))     # overwrites and new keys
        mt.put(int(rng.integers(0, 500)), 1, 2, 3)
        mt.snapshot()
    assert [a.tobytes() for a in held] == before


def test_caller_columns_are_copied():
    rng = np.random.default_rng(6)
    mt = Memtable(EngineConfig(engine="scavenger"))
    cols = _batch(rng, 64, 1000)
    mt.put_batch(*cols)
    for c in cols:
        c[:] = 0                                  # the caller reuses them
    _assert_same(mt.snapshot(), _rebuild(mt))


def test_key_column_identity():
    rng = np.random.default_rng(7)
    mt = Memtable(EngineConfig(engine="scavenger"))
    mt.put_batch(*_batch(rng, 256, 300))
    held = mt.snapshot()
    before = [a.tobytes() for a in held]
    keys0 = held[0]
    present = _batch(rng, 128, 300)
    present[0][:] = rng.choice(keys0, 128)       # only keys already there
    mt.put_batch(*present)
    mt.put(int(keys0[0]), 9, 9, 9)
    snap = mt.snapshot()
    assert snap[0] is keys0
    _assert_same(snap, _rebuild(mt))
    assert [a.tobytes() for a in held] == before
    mt.put(int(keys0[-1]) + 1, 10, 10, 10)       # a new key
    snap = mt.snapshot()
    assert snap[0] is not keys0
    _assert_same(snap, _rebuild(mt))


def test_restored_memtable_rebuilds_then_merges(tmp_path):
    cfg = EngineConfig.scaled("scavenger", 8 << 20, est_keys=1024)
    store = Store(cfg)
    rng = np.random.default_rng(8)
    store.write(WriteBatch().puts(rng.integers(0, 1024, 200).astype(
        np.uint64), np.full(200, 300, np.int64)))
    assert len(store.memtable)
    path = store.checkpoint(tmp_path / "snap.ckpt")
    meta, arrays = dsnap.read_snapshot(path)
    mt = dsnap._restore_memtable(cfg, arrays, "mt")
    assert not mt.snapshot_current and not mt.snapshot_merges
    assert mt.entries == store.memtable.entries
    _assert_same(mt.snapshot(), store.memtable.snapshot())
    first = int(mt.snapshot()[0][0])
    mt.put(5000, 10**6, 1, 100)                  # writes after the restore
    mt.delete(first, 10**6 + 1)
    assert mt.snapshot_merges
    _assert_same(mt.snapshot(), _rebuild(mt))
    assert 5000 in mt.snapshot()[0].tolist()
