"""One-pass value-record charge in ``read_values_batch`` (DESIGN.md §7).

The value fetch probes each vSST's whole fetch column in one scalar pass
(``BlockCache.probe_runs``) and charges the simulated I/O per coalesced
run.  It must leave exactly the state the per-run charge left: the same
float additions to the simulated clock in the same order, the same read
bytes and ops, the same cache hit/miss counters and LRU order.  The
per-run charge is kept below, verbatim, as the reference.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import EngineConfig, Store, WriteBatch
from repro.core import accel
from repro.core.engine import io as sio
from repro.core.engine.cache import BlockCache
from repro.core.engine.tables import ETYPE_REF, ETYPE_TOMB, SSTable
from repro.core.values import fetch
from repro.core.values.resolve import resolve_value_fids

N_KEYS = 4096
FRESH = np.arange(N_KEYS, N_KEYS + 24, dtype=np.uint64)   # one fresh vSST


# ------------------------------------------------- reference (per-run charge)
def split_runs(posu: np.ndarray, window: int | None) -> list[np.ndarray]:
    runs = np.split(posu, np.nonzero(np.diff(posu) != 1)[0] + 1)
    if window:
        runs = [c for r in runs
                for c in np.split(r, np.arange(window, len(r), window))]
    return runs


def probe_records(cache, fid, stream, positions, nbytes,
                  priority=BlockCache.PRI_LOW):
    hits = np.empty(len(positions), bool)
    for i, (p, nb) in enumerate(zip(np.asarray(positions).tolist(),
                                    np.asarray(nbytes).tolist())):
        ck = (fid, stream, p)
        if cache.get(ck):
            hits[i] = True
        else:
            hits[i] = False
            cache.put(ck, int(nb), priority)
    return hits


def reference_read_values_batch(store, keys, vids, vfiles, vsizes, cat,
                                strict: bool = False) -> None:
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
    uniq, first = np.unique(fsel, return_index=True)
    order = uniq[np.argsort(first)]
    window = store.cfg.coalesce_window
    pos_per_file = []
    for fid in order.tolist():
        t = store.version.value_files[fid]
        m = fsel == fid
        pos = accel.table_find(store, t, ksel[m])
        if pos is None:
            pos = t.find(ksel[m])
        if strict:
            assert (pos >= 0).all() and (t.vids[pos] == vsel[m]).all(), \
                "stale locator"
        else:
            pos = pos[pos >= 0]
        pos_per_file.append(pos)
    cat_rank = np.repeat(np.arange(len(order)),
                         [len(p) for p in pos_per_file])
    cat_pos = (np.concatenate(pos_per_file) if pos_per_file
               else np.zeros(0, np.int64))
    plan = accel.plan_runs(store, cat_rank, cat_pos)
    for i, fid in enumerate(order.tolist()):
        t = store.version.value_files[fid]
        if plan is None:
            posu = np.unique(pos_per_file[i])
            starts = None
        else:
            r_s, p_s, keep, start = plan
            sel = keep & (r_s == i)
            posu = p_s[sel]
            starts = start[sel]
        if len(posu) == 0:
            continue
        if t.layout == "rtable":
            for b in np.unique(t.index_block_of[posu]).tolist():
                store.read_block(t, "ib", b, cat, BlockCache.PRI_HIGH,
                                 t.index_block_bytes())
            runs = (split_runs(posu, window) if starts is None
                    else np.split(posu, np.nonzero(starts)[0][1:]))
            for r in runs:
                rb = t.rec_bytes[r]
                hits = probe_records(store.cache, t.fid, "rec", r, rb,
                                     BlockCache.PRI_LOW)
                nh = int(hits.sum())
                if nh:
                    store.io.cache_hit(cat, nh)
                nbytes = int(rb[~hits].sum())
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


# ------------------------------------------------------------------ harness
def build_store(planner: str, window: int | None) -> Store:
    """1 KB records over many small vSSTs, rewritten until GC has run and
    left inheritance chains, then a fresh vSST of 24 adjacent keys.  The
    cache holds about 200 records: a request both hits and evicts."""
    over = ({"use_kernels": False} if planner == "host"
            else {"kernel_min_batch": 1})
    cfg = EngineConfig.scaled("scavenger", 4 << 20, est_keys=N_KEYS,
                              coalesce_window=window, cache_bytes=256 << 10,
                              **over)
    store = Store(cfg)
    rng = np.random.default_rng(5)
    store.write(WriteBatch().puts(np.arange(N_KEYS, dtype=np.uint64),
                                  np.full(N_KEYS, 1024)))
    for _ in range(40):
        keys = rng.integers(0, N_KEYS, 256).astype(np.uint64)
        store.write(WriteBatch().puts(keys, np.full(256, 1024)))
    store.write(WriteBatch().puts(FRESH, np.full(len(FRESH), 1024)))
    store.flush()
    return store


def request(kind: str) -> np.ndarray:
    rng = np.random.default_rng(11)
    if kind == "spread":
        return rng.integers(0, N_KEYS, 256).astype(np.uint64)
    if kind == "dups":
        hot = rng.integers(0, N_KEYS, 24).astype(np.uint64)
        return hot[rng.integers(0, len(hot), 256)]
    return rng.permutation(FRESH)                     # "single_run"


def charge(store: Store, keys: np.ndarray, impl) -> None:
    """The value-fetch step of ``Store.multi_get`` for ``keys``."""
    with store.io.batched(len(keys)):
        res = store.lookup_entries(keys, sio.CAT_FG_READ)
        live = res["found"] & (res["etype"] != ETYPE_TOMB)
        refs = np.nonzero(live & (res["etype"] == ETYPE_REF))[0]
        impl(store, keys[refs], res["vid"][refs], res["vfile"][refs],
             res["vsize"][refs], sio.CAT_FG_READ, strict=True)


def record_advances(store: Store) -> list:
    """Log every advance of the simulated clock, in order."""
    log, advance = [], store.io._advance

    def logged(t, cat):
        log.append((t, cat))
        return advance(t, cat)

    store.io._advance = logged
    return log


def state(store: Store) -> dict:
    io, cache = store.io, store.cache
    return {"time_us": dict(io.time_us), "lanes": dict(io.lanes),
            "read_bytes": dict(io.read_bytes),
            "read_ops": dict(io.read_ops),
            "hits": cache.hits, "misses": cache.misses,
            "low": list(cache._low.items()),
            "high": list(cache._high.items())}


@pytest.mark.parametrize("kind", ["spread", "dups", "single_run"])
@pytest.mark.parametrize("planner", ["host", "routed"])
@pytest.mark.parametrize("window", [None, 4])
def test_one_pass_charge_matches_per_run_charge(window, planner, kind,
                                                monkeypatch):
    plans = []
    real_plan_runs = accel.plan_runs

    def spy(store, ranks, pos):
        out = real_plan_runs(store, ranks, pos)
        plans.append(out is not None)
        return out

    monkeypatch.setattr(accel, "plan_runs", spy)
    first_fid = SSTable._next_fid
    new = build_store(planner, window)
    monkeypatch.setattr(SSTable, "_next_fid", first_fid)   # same file ids
    ref = build_store(planner, window)
    assert state(new) == state(ref)
    assert len(new.version.value_files) >= 3 and new.chains
    keys = request(kind)
    new_log, ref_log = record_advances(new), record_advances(ref)
    for _ in range(2):                  # cold, then with resident records
        charge(new, keys, fetch.read_values_batch)
        charge(ref, keys, reference_read_values_batch)
        assert state(new) == state(ref)
    assert new_log == ref_log
    assert plans and all(p == (planner == "routed") for p in plans)
    assert new.cache.hits > 0 and new.cache.misses > 0
    if kind == "single_run":        # one vSST, one adjacency run
        res = new.lookup_entries(FRESH, sio.CAT_FG_READ)
        fids = resolve_value_fids(new, res["vfile"], FRESH, res["vid"])
        assert len(set(fids.tolist())) == 1
        pos = np.sort(new.version.value_files[int(fids[0])].find(FRESH))
        assert (np.diff(pos) == 1).all()


# ------------------------------------------------------------ unit: helpers
@pytest.mark.parametrize("window", [None, 1, 2, 3, 16])
@pytest.mark.parametrize("pairs", [
    [(0, 0)], [(1, 4), (1, 5), (1, 6), (1, 7), (1, 8), (1, 9), (1, 10)],
    [(0, 3), (0, 1), (2, 9), (0, 4), (2, 10), (0, 5), (0, 4), (2, 40)],
    [(r, p) for r in (2, 0, 1) for p in range(0, 60, 2)]])
def test_host_plan_matches_split(pairs, window):
    """``plan_runs_host`` gives, per file rank, the runs of the per-file
    ``np.unique`` + ``split_runs`` planner."""
    rank = np.array([r for r, _ in pairs], np.int64)
    pos = np.array([p for _, p in pairs], np.int64)
    r_s, p_s, keep, start = fetch.plan_runs_host(rank, pos, window)
    for r in np.unique(rank):
        sel = keep & (r_s == r)
        assert start[sel][0]
        runs = np.split(p_s[sel], np.nonzero(start[sel])[0][1:])
        want = split_runs(np.unique(pos[rank == r]), window)
        assert [x.tolist() for x in runs] == [x.tolist() for x in want]


def _starts(pos: list, window: int) -> list:
    """Run starts of a sorted position list: gaps, then every ``window``."""
    out, run = [], 0
    for i, p in enumerate(pos):
        run = 0 if i == 0 or p != pos[i - 1] + 1 or run == window - 1 \
            else run + 1
        out.append(run == 0)
    return out


@pytest.mark.parametrize("priority", [BlockCache.PRI_LOW,
                                      BlockCache.PRI_HIGH])
def test_probe_runs_is_the_get_put_sequence(priority):
    """Per-run (hits, miss bytes), LRU order and counters equal the
    record-by-record get-then-put-on-miss walk, evictions included."""
    rng = np.random.default_rng(3)
    a, b = BlockCache(5000), BlockCache(5000)
    for c in (a, b):
        for k in range(6):
            c.put((9, "rec", k), 300, BlockCache.PRI_HIGH)
    for _ in range(20):
        pos = np.unique(rng.integers(0, 30, 12)).tolist()
        nb = rng.integers(100, 900, len(pos)).tolist()
        starts = _starts(pos, 3)
        got = a.probe_runs(9, "rec", pos, nb, starts, priority)
        want, cur = [], None
        for p, n, s in zip(pos, nb, starts):
            if s:
                cur = [0, 0]
                want.append(cur)
            if b.get((9, "rec", p)):
                cur[0] += 1
            else:
                cur[1] += n
                b.put((9, "rec", p), n, priority)
        assert got == [tuple(r) for r in want]
        assert (a.hits, a.misses, a.low_bytes, a.high_bytes) == \
            (b.hits, b.misses, b.low_bytes, b.high_bytes)
        assert list(a._low.items()) == list(b._low.items())
        assert list(a._high.items()) == list(b._high.items())


def test_probe_runs_empty_column():
    c = BlockCache(1000)
    assert c.probe_runs(1, "rec", [], [], []) == []
    assert (c.hits, c.misses) == (0, 0)
