"""Memtable: in-memory sorted write buffer (dict + sort-at-flush,
DESIGN.md §2).

Entries are (seq, etype, vid, vsize, vfile).  Normal user puts are INLINE
(the memtable holds the full value until flush decides separation); Titan's
GC Write-Index puts REF entries pointing at an existing blob file.

Reads go through a cached *columnar snapshot* (key-sorted parallel arrays):
``get_batch`` probes a whole key column with one ``searchsorted``, and
scans slice key ranges out of the same arrays — no per-key Python in the
batched read path.  Writes append their records to a list of pending
delta columns and leave the snapshot in place; the next ``snapshot()``
merges the pending rows into it (a sort of the delta, a ``searchsorted``
into the snapshot's keys, overwrites and ``np.insert``), so a read after
a write costs the size of the write, not of the memtable.  Only a
memtable whose ``entries`` were filled directly (a restore) rebuilds its
snapshot from the dict.  A merge never writes into an array it handed
out (device copies are cached on the host array's identity): it returns
new columns, and keeps the key column itself when no key is new.
"""

from __future__ import annotations

import numpy as np

from .config import EngineConfig
from .tables import ETYPE_INLINE, ETYPE_REF, ETYPE_TOMB


# dtypes of the snapshot's columns: keys, seqs, etype, vids, vsizes, vfiles
COLUMN_DTYPES = (np.uint64, np.uint64, np.uint8, np.uint64, np.int64,
                 np.int64)


class Memtable:
    def __init__(self, cfg: EngineConfig):
        self.cfg = cfg
        # key -> (seq, etype, vid, vsize, vfile)
        self.entries: dict[int, tuple] = {}
        self.bytes = 0
        # columnar snapshot of ``entries`` less the pending rows; None
        # after a restore filled ``entries`` directly
        self._snap: tuple | None = tuple(np.empty(0, dt)
                                         for dt in COLUMN_DTYPES)
        self._pending: list[tuple] = []     # delta columns, arrival order

    def _entry_bytes(self, etype: int, vsize: int) -> int:
        if etype == ETYPE_TOMB:
            return self.cfg.tomb_rec_bytes()
        if etype == ETYPE_REF:
            return self.cfg.ref_rec_bytes()
        return self.cfg.inline_rec_bytes(vsize)

    def _set(self, key: int, entry: tuple) -> None:
        prev = self.entries.get(key)
        if prev is not None:
            self.bytes -= self._entry_bytes(prev[1], prev[3])
        self.entries[key] = entry
        self.bytes += self._entry_bytes(entry[1], entry[3])
        self._pending.append(tuple(
            np.array([v], dt) for v, dt in zip((key, *entry), COLUMN_DTYPES)))

    def put(self, key: int, seq: int, vid: int, vsize: int) -> None:
        self._set(key, (seq, ETYPE_INLINE, vid, vsize, -1))

    def put_ref(self, key: int, seq: int, vid: int, vsize: int,
                vfile: int) -> None:
        self._set(key, (seq, ETYPE_REF, vid, vsize, vfile))

    def delete(self, key: int, seq: int) -> None:
        self._set(key, (seq, ETYPE_TOMB, 0, 0, -1))

    def get(self, key: int):
        return self.entries.get(key)

    def drop_snapshot(self) -> None:
        """Forget the snapshot after ``entries`` was filled directly: the
        next ``snapshot()`` rebuilds it from the dict."""
        self._snap = None
        self._pending = []

    @property
    def snapshot_current(self) -> bool:
        """True while the cached snapshot matches the entries (no write
        since it was built): ``snapshot()`` then does nothing."""
        return self._snap is not None and not self._pending

    @property
    def snapshot_merges(self) -> bool:
        """True when ``snapshot()`` will merge pending rows into the cached
        snapshot (False: nothing to do, or a rebuild from the dict)."""
        return self._snap is not None and bool(self._pending)

    def snapshot(self) -> tuple:
        """Key-sorted columnar view: (keys, seqs, etype, vids, vsizes,
        vfiles) parallel arrays, with the pending writes merged in."""
        if self._snap is None:
            self._pending = []
            n = len(self.entries)
            keys = np.fromiter(self.entries.keys(), np.uint64, count=n)
            order = np.argsort(keys, kind="stable")
            vals = list(self.entries.values())
            self._snap = (keys[order],) + tuple(
                np.fromiter((v[i] for v in vals), dt, count=n)[order]
                for i, dt in enumerate(COLUMN_DTYPES[1:]))
        elif self._pending:
            self._snap = self._merge()
        return self._snap

    def _merge(self) -> tuple:
        """The snapshot with the pending rows merged in, last write per
        key winning; new arrays throughout, except the key column when
        no key is new."""
        delta = [np.concatenate(c) for c in zip(*self._pending)]
        self._pending = []
        order = np.argsort(delta[0], kind="stable")
        dk = delta[0][order]
        last = np.ones(len(dk), bool)
        last[:-1] = dk[1:] != dk[:-1]
        delta = [c[order[last]] for c in delta]
        base = self._snap
        bk, dk = base[0], delta[0]
        pos = np.searchsorted(bk, dk)
        hit = pos < len(bk)
        hit[hit] = bk[pos[hit]] == dk[hit]
        new = ~hit
        if new.any():
            at_new = pos[new]
            cols = [np.insert(b, at_new, d[new]) for b, d in zip(base, delta)]
            # a present key shifts right by the new keys inserted before it
            at = pos[hit] + np.searchsorted(at_new, pos[hit], side="right")
        else:
            cols = [bk] + [b.copy() for b in base[1:]]
            at = pos[hit]
        for c, d in zip(cols[1:], delta[1:]):
            c[at] = d[hit]
        return tuple(cols)

    def get_batch(self, keys: np.ndarray) -> tuple:
        """Vectorized point probe for a key column.

        Returns (found, seqs, etype, vids, vsizes, vfiles) parallel arrays
        aligned with ``keys``; rows where ``found`` is False hold the
        safe-gather placeholder and must be masked by the caller."""
        mk, seqs, ety, vids, vsz, vf = self.snapshot()
        nq = len(keys)
        if len(mk) == 0:
            return (np.zeros(nq, bool), np.zeros(nq, np.uint64),
                    np.zeros(nq, np.uint8), np.zeros(nq, np.uint64),
                    np.zeros(nq, np.int64), np.zeros(nq, np.int64))
        pos = np.searchsorted(mk, keys)
        ok = pos < len(mk)
        safe = np.where(ok, pos, 0)
        ok &= mk[safe] == keys
        return (ok, seqs[safe], ety[safe], vids[safe], vsz[safe], vf[safe])

    def entry_bytes_batch(self, ety: np.ndarray, vsizes: np.ndarray
                          ) -> np.ndarray:
        """Vectorized serialized-size computation for a record column."""
        return np.where(
            ety == ETYPE_TOMB, self.cfg.tomb_rec_bytes(),
            np.where(ety == ETYPE_REF, self.cfg.ref_rec_bytes(),
                     self.cfg.inline_rec_bytes(vsizes))).astype(np.int64)

    def put_batch(self, keys: np.ndarray, seqs: np.ndarray, ety: np.ndarray,
                  vids: np.ndarray, vsizes: np.ndarray, vfiles: np.ndarray,
                  entry_bytes: np.ndarray | None = None) -> int:
        """Insert a record column until the memtable fills.

        Returns how many records were consumed (always >= 1 on non-empty
        input); the caller rotates the memtable and re-submits the rest.
        Stops exactly where the scalar path would have rotated, so batch
        and scalar runs produce identical flush boundaries.
        """
        n = len(keys)
        if n == 0:
            return 0
        if entry_bytes is None:
            entry_bytes = self.entry_bytes_batch(ety, vsizes)
        cap = self.cfg.memtable_bytes
        entries = self.entries
        consumed = 0
        for k, rec, nbytes in zip(
                keys.tolist(),
                zip(seqs.tolist(), ety.tolist(), vids.tolist(),
                    vsizes.tolist(), vfiles.tolist()),
                entry_bytes.tolist()):
            prev = entries.get(k)
            if prev is not None:
                self.bytes -= self._entry_bytes(prev[1], prev[3])
            entries[k] = rec
            self.bytes += nbytes
            consumed += 1
            if self.bytes >= cap:
                break
        # copies: the caller's columns are slices of its request
        self._pending.append(tuple(
            np.array(c[:consumed], dt) for c, dt in zip(
                (keys, seqs, ety, vids, vsizes, vfiles), COLUMN_DTYPES)))
        return consumed

    @property
    def full(self) -> bool:
        return self.bytes >= self.cfg.memtable_bytes

    def __len__(self) -> int:
        return len(self.entries)

    def sorted_arrays(self):
        """-> (keys, seqs, etype, vids, vsizes, vfiles) sorted by key."""
        return self.snapshot()
