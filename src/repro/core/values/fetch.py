"""Coalesced value fetch planning for multi_get / scans (paper §III-B.1;
DESIGN.md §7, §12).

Vectorized planning: one inheritance-chain resolution pass for the whole
locator column, one ``find`` per touched vSST (not per record), record
fetches coalesced into adjacent-position runs — one random I/O per run,
optionally capped at ``EngineConfig.coalesce_window`` records per run.
Runs are planned in one pass over the whole (file-rank, position) column
in first-occurrence file order: sort, dedup, mark run starts.  Eligible
batches plan through the ``run_coalesce`` kernel (``core/accel.py``), the
rest through ``plan_runs_host``, with identical output.

Each file's kept positions are then charged in one scalar pass
(``BlockCache.probe_runs``): per-record cache residency and LRU order are
inherently per-entry, and that pass is the one per-record step the
byte-parity contract keeps.  The simulated I/O follows per run, in run
order: cache hits, then one random read of the missed bytes.
"""

from __future__ import annotations

import numpy as np

from .. import accel
from ..engine.cache import BlockCache
from .resolve import resolve_value_fids


def plan_runs_host(rank: np.ndarray, pos: np.ndarray,
                   window: int | None):
    """The host planner, one pass over the whole (file-rank, position)
    column: the ``run_coalesce`` kernel's output, computed with NumPy.

    -> (rank_s, pos_s, keep, run_start), sorted by (rank, pos); duplicates
    have keep False, and run_start marks the first kept record of each
    adjacent run, capped at ``window`` kept records when set."""
    o = np.lexsort((pos, rank))
    r, p = rank[o], pos[o]
    keep = np.ones(len(r), bool)
    keep[1:] = (r[1:] != r[:-1]) | (p[1:] != p[:-1])
    start = keep.copy()
    start[1:] &= (r[1:] != r[:-1]) | (p[1:] - p[:-1] > 1)
    if window:
        kept = np.cumsum(keep)
        base = np.maximum.accumulate(np.where(start, kept, 0))
        start |= keep & ((kept - base) % window == 0)
    return r, p, keep, start


def read_values_batch(store, keys, vids, vfiles, vsizes, cat,
                      strict: bool = False) -> None:
    """Charge the I/O for fetching value records of resolved entries.

    ``strict`` (multi_get): every entry won a newest-wins lookup, so an
    unresolvable file or vid mismatch means GC dropped live data.  Scans
    stay lenient: a truncated scan pass can surface a superseded REF whose
    record GC already reclaimed — the scan retry loop re-runs it with a
    larger limit."""
    n = len(keys)
    if n == 0:
        return
    keys = np.asarray(keys, np.uint64)
    vids = np.asarray(vids, np.uint64)
    fids = resolve_value_fids(store, vfiles, keys, vids)
    if strict:
        assert (fids >= 0).all(), "value file for live key lost"
    ok = fids >= 0
    if not ok.any():
        return
    fsel, ksel, vsel = fids[ok], keys[ok], vids[ok]
    uniq, first, inv = np.unique(fsel, return_index=True,
                                 return_inverse=True)
    by_first = np.argsort(first)
    order = uniq[by_first].tolist()                 # first-occurrence order
    rank_of = np.empty(len(uniq), np.int64)
    rank_of[by_first] = np.arange(len(uniq))
    row_rank = rank_of[inv]
    grouped = np.argsort(row_rank, kind="stable")    # rows by file rank
    ksel, vsel = ksel[grouped], vsel[grouped]
    lo = np.searchsorted(row_rank[grouped],
                         np.arange(len(order) + 1)).tolist()
    pos_per_file = []
    # one ``find`` per unique vSST — structure-bounded  # scavlint: allow-loop
    for i, fid in enumerate(order):
        t = store.version.value_files[fid]
        kf = ksel[lo[i]:lo[i + 1]]
        pos = accel.table_find(store, t, kf)
        if pos is None:
            pos = t.find(kf)
        if strict:
            assert (pos >= 0).all() and \
                (t.vids[pos] == vsel[lo[i]:lo[i + 1]]).all(), "stale locator"
        else:
            pos = pos[pos >= 0]
        pos_per_file.append(pos)
    cat_rank = np.repeat(np.arange(len(order)),
                         [len(p) for p in pos_per_file])
    cat_pos = np.concatenate(pos_per_file)
    plan = accel.plan_runs(store, cat_rank, cat_pos)
    if plan is None:
        plan = plan_runs_host(cat_rank, cat_pos, store.cfg.coalesce_window)
    r_s, p_s, keep, start = plan
    r_k, p_k, s_k = r_s[keep], p_s[keep], start[keep]
    bounds = np.searchsorted(r_k, np.arange(len(order) + 1)).tolist()
    # one vSST per unique fid — structure-bounded  # scavlint: allow-loop
    for i, fid in enumerate(order):                 # first-occurrence order
        t = store.version.value_files[fid]
        posu = p_k[bounds[i]:bounds[i + 1]]
        if len(posu) == 0:
            continue
        if t.layout == "rtable":
            for b in sorted(set(t.index_block_of[posu].tolist())):
                store.read_block(t, "ib", b, cat, BlockCache.PRI_HIGH,
                                 t.index_block_bytes())
            runs = store.cache.probe_runs(
                t.fid, "rec", posu.tolist(), t.rec_bytes[posu].tolist(),
                s_k[bounds[i]:bounds[i + 1]].tolist(), BlockCache.PRI_LOW)
            for nh, nbytes in runs:                 # run order
                if nh:
                    store.io.cache_hit(cat, nh)
                if nbytes:
                    store.io.rand_read(nbytes, cat)
        else:
            store.read_block(t, "i", 0, cat, BlockCache.PRI_HIGH,
                             t.index_block_bytes())
            blocks = t.block_of[posu]
            for b in np.unique(blocks).tolist():
                mm = posu[blocks == b]
                nb = max(int(t.rec_bytes[mm].max()),
                         t.data_block_bytes(0, b))
                store.read_block(t, "d0", b, cat, BlockCache.PRI_LOW, nb)
