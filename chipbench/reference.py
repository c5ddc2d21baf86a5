"""Plain last-write-wins reference of the store's answers.

It imports nothing of the program.  It holds, per key, the value identity
(``vid``) and size that the newest acknowledged write of the key returned,
in one sorted key column.  Within a WriteBatch the last record of a key
wins, as the store documents for ``Store.write``.
"""

from __future__ import annotations

import numpy as np


class LastWriteWins:
    def __init__(self, key_bytes: int):
        self.key_bytes = key_bytes
        self.keys = np.zeros(0, np.uint64)
        self.vids = np.zeros(0, np.uint64)
        self.vsizes = np.zeros(0, np.int64)
        self.live_bytes = 0     # sum over live keys of key + value bytes

    def apply(self, keys, vids, vsizes) -> None:
        """One acknowledged WriteBatch of puts."""
        keys = np.asarray(keys, np.uint64)
        n = len(keys)
        if n == 0:
            return
        # last occurrence of each key in batch order
        rev = np.unique(keys[::-1], return_index=True)[1]
        at = n - 1 - rev
        bk = keys[at]
        bv = np.asarray(vids, np.uint64)[at]
        bs = np.asarray(vsizes, np.int64)[at]
        pos = np.searchsorted(self.keys, bk)
        safe = np.minimum(pos, max(len(self.keys) - 1, 0))
        old = (pos < len(self.keys)) & (self.keys[safe] == bk
                                        if len(self.keys) else False)
        self.live_bytes -= int(self.vsizes[pos[old]].sum()) \
            + self.key_bytes * int(old.sum())
        self.live_bytes += int(bs.sum()) + self.key_bytes * len(bk)
        self.vids[pos[old]] = bv[old]
        self.vsizes[pos[old]] = bs[old]
        new = ~old
        if new.any():
            self.keys = np.insert(self.keys, pos[new], bk[new])
            self.vids = np.insert(self.vids, pos[new], bv[new])
            self.vsizes = np.insert(self.vsizes, pos[new], bs[new])

    def get(self, keys):
        """-> (found, vid, vsize) per key; vid and vsize 0 where absent."""
        keys = np.asarray(keys, np.uint64)
        if len(self.keys) == 0:
            z = np.zeros(len(keys), np.int64)
            return np.zeros(len(keys), bool), z.astype(np.uint64), z
        pos = np.minimum(np.searchsorted(self.keys, keys),
                         len(self.keys) - 1)
        found = self.keys[pos] == keys
        return (found, np.where(found, self.vids[pos], 0).astype(np.uint64),
                np.where(found, self.vsizes[pos], 0).astype(np.int64))

    def scan(self, start: int, count: int) -> list:
        """Up to ``count`` (key, vid) pairs from the first key >= start."""
        a = int(np.searchsorted(self.keys, np.uint64(max(0, start))))
        b = min(a + int(count), len(self.keys))
        return list(zip(self.keys[a:b].tolist(), self.vids[a:b].tolist()))
