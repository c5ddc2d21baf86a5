"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes/dtypes + hypothesis property tests.  The routed kernels
(lookup_probe, segment_reduce) are also checked at shapes whose streamed
columns span several VMEM blocks (``common.BLOCK_ROWS``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose, assert_array_equal

from _hypothesis_support import given, settings, st

from repro.kernels import (bloom_build, bloom_probe, bloom_probe_ref,
                           gather_min64, gc_lookup, gc_lookup_ref,
                           hot_cold_partition, hot_cold_partition_ref,
                           interval_rank, lookup_probe, merge_dedup,
                           merge_dedup_ref, page_gather, page_gather_ref,
                           rank_probe, run_coalesce, segment_sum)
from repro.kernels.common import bitonic_merge, bitonic_sort

# kernels.lookup_probe / kernels.segment_reduce ops run in both modes: the
# jitted XLA oracle and the Pallas interpreter.
MODES = ("xla", "interpret")

# largest u32 value the dispatchers accept (pad sentinel is 0xFFFFFFFE)
BOUNDARY = 0xFFFFFFFD


# ------------------------------------------------------------- common nets
@pytest.mark.parametrize("n", [2, 8, 64, 256])
def test_bitonic_sort_matches_numpy(n):
    rng = np.random.default_rng(n)
    keys = rng.integers(0, 1000, n).astype(np.uint32)
    payload = np.arange(n, dtype=np.uint32)
    k, p = bitonic_sort(jnp.asarray(keys), jnp.asarray(payload))
    assert_array_equal(np.sort(keys), np.asarray(k))
    # payload follows its key
    assert_array_equal(keys[np.asarray(p)], np.asarray(k))


def test_bitonic_merge_of_two_sorted_runs():
    rng = np.random.default_rng(0)
    a = np.sort(rng.integers(0, 500, 32)).astype(np.uint32)
    b = np.sort(rng.integers(0, 500, 32)).astype(np.uint32)
    seq = np.concatenate([a, b[::-1]]).astype(np.uint32)
    (merged,) = bitonic_merge(jnp.asarray(seq))
    assert_array_equal(np.sort(np.concatenate([a, b])), np.asarray(merged))


# --------------------------------------------------------------- gc_lookup
@pytest.mark.parametrize("q,n", [(1, 10), (17, 100), (300, 1000),
                                 (256, 512), (5, 2000)])
def test_gc_lookup_matches_ref(q, n):
    rng = np.random.default_rng(q * 1000 + n)
    s_keys = np.sort(rng.choice(np.arange(1, 10 * n, dtype=np.uint32),
                                size=n, replace=False))
    s_vids = rng.integers(1, 1 << 30, n).astype(np.uint32)
    s_vf = rng.integers(1, 1 << 20, n).astype(np.uint32)
    queries = np.concatenate([
        rng.choice(s_keys, q // 2 + 1),
        rng.integers(10 * n, 20 * n, q - q // 2 - 1).astype(np.uint32)])[:q]
    got = gc_lookup(queries, s_keys, s_vids, s_vf)
    want = gc_lookup_ref(jnp.asarray(queries), jnp.asarray(s_keys),
                         jnp.asarray(s_vids), jnp.asarray(s_vf))
    for g, w in zip(got, want):
        assert_array_equal(np.asarray(g), np.asarray(w))


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 2**20), min_size=1, max_size=200, unique=True),
       st.lists(st.integers(0, 2**20), min_size=1, max_size=100))
def test_gc_lookup_property(skeys, queries):
    s_keys = np.sort(np.array(skeys, np.uint32))
    s_vids = s_keys + 7
    s_vf = s_keys % 97
    q = np.array(queries, np.uint32)
    found, vid, vf = gc_lookup(q, s_keys, s_vids, s_vf)
    member = np.isin(q, s_keys)
    assert_array_equal(np.asarray(found), member)
    assert_array_equal(np.asarray(vid)[member], (q + 7)[member])


# ------------------------------------------------------------------- bloom
@pytest.mark.parametrize("n,q", [(10, 5), (1000, 300), (5000, 1000)])
def test_bloom_probe_matches_ref_and_no_false_negatives(n, q):
    rng = np.random.default_rng(n)
    keys = rng.choice(np.arange(1, 1 << 24, dtype=np.uint32), n,
                      replace=False)
    words, k, nbits = bloom_build(keys)
    probes = np.concatenate([keys[:q // 2],
                             rng.integers(1 << 24, 1 << 25,
                                          q - q // 2).astype(np.uint32)])
    got = np.asarray(bloom_probe(probes, words, k, nbits))
    want = np.asarray(bloom_probe_ref(jnp.asarray(probes), words, k, nbits))
    assert_array_equal(got, want)
    assert got[:q // 2].all(), "bloom false negative!"
    fp = got[q // 2:].mean()
    assert fp < 0.1


# ------------------------------------------------------------------- merge
@pytest.mark.parametrize("na,nb", [(1, 1), (10, 3), (100, 100), (64, 257)])
def test_merge_dedup_matches_ref(na, nb):
    rng = np.random.default_rng(na * 97 + nb)
    ak = np.sort(rng.choice(np.arange(1000, dtype=np.uint32), na,
                            replace=False))
    bk = np.sort(rng.choice(np.arange(1000, dtype=np.uint32), nb,
                            replace=False))
    aseq = rng.integers(0, 1000, na).astype(np.uint32) * 2        # even
    bseq = rng.integers(0, 1000, nb).astype(np.uint32) * 2 + 1    # odd
    avid = rng.integers(0, 1 << 30, na).astype(np.uint32)
    bvid = rng.integers(0, 1 << 30, nb).astype(np.uint32)
    gk, gs, gv, gkeep = merge_dedup(ak, aseq, avid, bk, bseq, bvid)
    wk, ws, wv, wkeep = merge_dedup_ref(
        jnp.asarray(ak), jnp.asarray(aseq), jnp.asarray(avid),
        jnp.asarray(bk), jnp.asarray(bseq), jnp.asarray(bvid))
    # compare surviving rows (sorted by key) — orderings within dup pairs
    # may differ, winners must not
    got = sorted(zip(np.asarray(gk)[np.asarray(gkeep)].tolist(),
                     np.asarray(gs)[np.asarray(gkeep)].tolist(),
                     np.asarray(gv)[np.asarray(gkeep)].tolist()))
    want = sorted(zip(np.asarray(wk)[np.asarray(wkeep)].tolist(),
                      np.asarray(ws)[np.asarray(wkeep)].tolist(),
                      np.asarray(wv)[np.asarray(wkeep)].tolist()))
    assert got == want
    # merged keys are sorted
    assert (np.diff(np.asarray(gk)) >= 0).all()


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(0, 50), min_size=1, max_size=60, unique=True),
       st.lists(st.integers(0, 50), min_size=1, max_size=60, unique=True))
def test_merge_dedup_property_newest_wins(akeys, bkeys):
    ak = np.sort(np.array(akeys, np.uint32))
    bk = np.sort(np.array(bkeys, np.uint32))
    aseq = np.full(len(ak), 10, np.uint32)
    bseq = np.full(len(bk), 20, np.uint32)       # b is newer
    avid = ak + 1
    bvid = bk + 2
    gk, gs, gv, keep = merge_dedup(ak, aseq, avid, bk, bseq, bvid)
    kept = {int(k): int(v) for k, v in
            zip(np.asarray(gk)[np.asarray(keep)],
                np.asarray(gv)[np.asarray(keep)])}
    expect = {int(k): int(k) + 1 for k in ak}
    expect.update({int(k): int(k) + 2 for k in bk})   # newer b wins
    assert kept == expect


# --------------------------------------------------------------- partition
@pytest.mark.parametrize("n", [1, 7, 64, 500])
def test_partition_matches_ref(n):
    rng = np.random.default_rng(n)
    keys = rng.integers(0, 1 << 30, n).astype(np.uint32)
    hot = rng.random(n) < 0.3
    vids = rng.integers(0, 1 << 30, n).astype(np.uint32)
    vsz = rng.integers(1, 1 << 16, n).astype(np.uint32)
    gk, gv, gs, gcnt = hot_cold_partition(keys, hot, vids, vsz)
    wk, wv, ws, wcnt = hot_cold_partition_ref(
        jnp.asarray(keys), jnp.asarray(hot), jnp.asarray(vids),
        jnp.asarray(vsz))
    assert int(gcnt) == int(wcnt) == hot.sum()
    assert_array_equal(np.asarray(gk), np.asarray(wk))
    assert_array_equal(np.asarray(gv), np.asarray(wv))
    assert_array_equal(np.asarray(gs), np.asarray(ws))


# ------------------------------------------------------------ paged gather
@pytest.mark.parametrize("b,p,npages,psize,d,dtype", [
    (1, 1, 4, 8, 128, jnp.float32),
    (4, 8, 64, 16, 128, jnp.float32),
    (2, 4, 16, 8, 64, jnp.bfloat16),
    (3, 5, 32, 4, 256, jnp.int32),
])
def test_page_gather_matches_ref(b, p, npages, psize, d, dtype):
    rng = np.random.default_rng(b * 100 + p)
    pages = jnp.asarray(
        rng.standard_normal((npages, psize, d)) * 10).astype(dtype)
    table = rng.integers(0, npages, (b, p)).astype(np.int32)
    got = page_gather(table, pages)
    want = page_gather_ref(jnp.asarray(table), pages)
    assert got.shape == (b, p * psize, d)
    assert_array_equal(np.asarray(got.astype(jnp.float32)),
                       np.asarray(want.astype(jnp.float32)))


# ----------------------------------------------- lookup_probe (fused read)
def _rank_oracle(queries, table):
    pos = np.searchsorted(table, queries)
    ok = pos < len(table)
    safe = np.where(ok, pos, 0)
    ok &= len(table) > 0 and table[safe] == queries
    return ok, pos


def _bloom_oracle(bit_idx, words):
    w = words[bit_idx >> 5]
    return (((w >> (bit_idx & 31)) & 1) == 1).all(axis=1)


def _probe_case(rng, q, n, boundary=False):
    """Adversarial (queries, table, bit_idx, words) quadruple."""
    space = np.arange(1, 4 * n + 2, dtype=np.uint32)
    table = np.sort(rng.choice(space, n, replace=False))
    if boundary and n:
        table[-1] = BOUNDARY
    queries = np.concatenate([
        rng.choice(table, q // 2 + 1) if n else np.zeros(1, np.uint32),
        rng.integers(4 * n + 2, 8 * n + 9, q).astype(np.uint32)])[:q]
    if boundary and q:
        queries[0] = BOUNDARY
    k, nbits = 7, 1 << 14
    words = rng.integers(0, 1 << 32, nbits // 32, dtype=np.uint64)
    words = words.astype(np.uint32)
    bit_idx = rng.integers(0, nbits, (q, k)).astype(np.uint32)
    return queries, table, bit_idx, words


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("q,n", [(0, 16), (1, 1), (7, 300), (256, 512),
                                 (300, 1000)])
def test_lookup_probe_matches_oracle(q, n, mode):
    if mode == "interpret" and q * n > 4096:
        pytest.skip("interpret mode: small shapes only")
    rng = np.random.default_rng(q * 1000 + n)
    queries, table, bit_idx, words = _probe_case(rng, q, n, boundary=True)
    may, found, rank = lookup_probe(queries, table, bit_idx, words,
                                    mode=mode)
    assert_array_equal(may, _bloom_oracle(bit_idx, words))
    wf, wr = _rank_oracle(queries, table)
    assert_array_equal(found, wf)
    assert_array_equal(rank[found], wr[found])


@pytest.mark.parametrize("mode", MODES)
def test_rank_probe_all_duplicates(mode):
    table = np.array([5, 9, 1000], np.uint32)
    queries = np.full(9, 9, np.uint32)          # all-duplicate batch
    found, rank = rank_probe(queries, table, mode=mode)
    assert found.all() and (rank == 1).all()


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, BOUNDARY), min_size=1, max_size=64,
                unique=True),
       st.lists(st.integers(0, BOUNDARY), min_size=0, max_size=64))
def test_rank_probe_property(tkeys, queries):
    table = np.sort(np.array(tkeys, np.uint32))
    q = np.array(queries, np.uint32)
    found, rank = rank_probe(q, table, mode="xla")
    wf, wr = _rank_oracle(q, table)
    assert_array_equal(found, wf)
    assert_array_equal(rank[found], wr[found])


@pytest.mark.parametrize("mode", MODES)
def test_interval_rank_matches_assign_files(mode):
    # disjoint sorted [min, max] file ranges, like an LSM level
    mins = np.array([10, 40, 100, 1000], np.uint64)
    maxs = np.array([30, 60, 900, BOUNDARY], np.uint64)
    queries = np.array([0, 10, 30, 31, 40, 99, 100, 900, 901, 1000,
                        BOUNDARY], np.uint64)
    got = interval_rank(queries, mins, maxs, mode=mode)
    pos = np.searchsorted(mins, queries, side="right") - 1
    ok = pos >= 0
    safe = np.where(ok, pos, 0)
    ok &= queries <= maxs[safe]
    assert_array_equal(got, np.where(ok, pos, -1))


# runs, filters and level bounds whose lane-dense columns span several
# VMEM blocks (BLOCK_ROWS * 128 = 32768 entries per block), and filters
# whose block count differs from the run's
@pytest.mark.parametrize("q,n,nwords", [(256, 40_000, 512),
                                        (700, 70_000, 40_000),
                                        (300, 1024, 70_000)])
def test_lookup_probe_streamed_blocks(q, n, nwords):
    rng = np.random.default_rng(n + nwords)
    space = np.arange(1, 4 * n + 2, dtype=np.uint32)
    table = np.sort(rng.choice(space, n, replace=False))
    table[-1] = BOUNDARY
    queries = np.concatenate([
        rng.choice(table, q // 2),
        rng.integers(0, 4 * n + 9, q - q // 2).astype(np.uint32)])
    queries[0] = BOUNDARY
    words = rng.integers(0, 1 << 32, nwords, dtype=np.uint64)
    words = words.astype(np.uint32)
    bit_idx = rng.integers(0, 32 * nwords, (q, 7)).astype(np.uint32)
    bit_idx[:, 0] = 32 * nwords - 1             # last bit of the last word
    may, found, rank = lookup_probe(queries, table, bit_idx, words,
                                    mode="interpret")
    assert_array_equal(may, _bloom_oracle(bit_idx, words))
    wf, wr = _rank_oracle(queries, table)
    assert_array_equal(found, wf)
    assert_array_equal(rank, wr)
    f2, r2 = rank_probe(queries, table, mode="interpret")
    assert_array_equal(f2, wf)
    assert_array_equal(r2, wr)


@pytest.mark.parametrize("n_files", [600, 1024, 40_000])
def test_interval_rank_streamed_blocks(n_files):
    """count_le over level bounds of >= 1024 files (several vreg rows, and
    several VMEM blocks at 40k)."""
    rng = np.random.default_rng(n_files)
    e = np.sort(rng.choice(np.arange(0, 8 * n_files, dtype=np.uint64),
                           2 * n_files, replace=False))
    mins, maxs = e[0::2], e[1::2]
    q = np.concatenate([mins[::7], maxs[::5], rng.integers(
        0, 8 * n_files, 300).astype(np.uint64)])
    got = interval_rank(q, mins, maxs, mode="interpret")
    pos = np.searchsorted(mins, q, side="right") - 1
    safe = np.where(pos >= 0, pos, 0)
    ok = (pos >= 0) & (q <= maxs[safe])
    assert_array_equal(got, np.where(ok, pos, -1))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 10_000), min_size=2, max_size=40,
                unique=True),
       st.lists(st.integers(0, 11_000), min_size=1, max_size=50))
def test_interval_rank_property(bounds, queries):
    e = np.sort(np.array(bounds, np.uint64))
    mins, maxs = e[::2][:len(e) // 2], e[1::2][:len(e) // 2]
    q = np.array(queries, np.uint64)
    got = interval_rank(q, mins, maxs, mode="xla")
    for qi, gi in zip(q.tolist(), got.tolist()):
        covers = np.nonzero((mins <= qi) & (qi <= maxs))[0]
        assert gi == (covers[0] if len(covers) else -1)


# -------------------------------------------- run_coalesce (fetch planning)
def _coalesce_oracle(rank, pos, window):
    """Per-rank np.unique + adjacency split + window chunking: the run
    plan ``run_coalesce`` and the host planner must both give."""
    out = []
    for r in np.unique(rank):
        posu = np.unique(pos[rank == r])
        runs = np.split(posu, np.nonzero(np.diff(posu) != 1)[0] + 1)
        if window:
            runs = [c for run in runs
                    for c in np.split(run, np.arange(window, len(run),
                                                     window))]
        out.append((int(r), [c.tolist() for c in runs]))
    return out


def _runs_from_kernel(rank_s, pos_s, keep, start):
    out = []
    for r in np.unique(rank_s[keep]):
        sel = keep & (rank_s == r)
        runs = np.split(pos_s[sel], np.nonzero(start[sel])[0][1:])
        out.append((int(r), [c.tolist() for c in runs]))
    return out


def _coalesce_case(case, window, m):
    rng = np.random.default_rng(hash((case, window, m)) % (1 << 32))
    if case == "empty":
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    if case == "single":
        return np.array([3]), np.array([77])
    if case == "dups":                          # all-duplicate positions
        return np.zeros(m, np.int64), np.full(m, 5, np.int64)
    return rng.integers(0, 5, m), rng.integers(0, m // 2, m)  # odd length


@pytest.mark.parametrize("m", [100, 4097])
@pytest.mark.parametrize("window", [None, 1, 3, 16])
@pytest.mark.parametrize("case", ["empty", "single", "dups", "mixed"])
def test_run_coalesce_matches_host_planner(case, window, m):
    rank, pos = _coalesce_case(case, window, m)
    got = run_coalesce(rank, pos, window=window)
    assert _runs_from_kernel(*got) == _coalesce_oracle(rank, pos, window)


@pytest.mark.parametrize("m", [100, 4097])
@pytest.mark.parametrize("window", [None, 1, 3, 16])
@pytest.mark.parametrize("case", ["empty", "single", "dups", "mixed"])
def test_host_planner_matches_run_coalesce(case, window, m):
    """``plan_runs_host`` (core/values/fetch.py) returns the kernel's
    arrays: same order, same keep and run-start masks."""
    from repro.core.values.fetch import plan_runs_host
    rank, pos = _coalesce_case(case, window, m)
    host = plan_runs_host(rank, pos, window)
    for h, k in zip(host, run_coalesce(rank, pos, window=window)):
        assert_array_equal(h, k)
    assert _runs_from_kernel(*host) == _coalesce_oracle(rank, pos, window)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 4), st.integers(0, 30)),
                min_size=1, max_size=80),
       st.sampled_from([None, 1, 2, 7]))
def test_run_coalesce_property(pairs, window):
    rank = np.array([p[0] for p in pairs], np.int64)
    pos = np.array([p[1] for p in pairs], np.int64)
    got = run_coalesce(rank, pos, window=window)
    assert _runs_from_kernel(*got) == _coalesce_oracle(rank, pos, window)


# -------------------------------------------- segment_reduce (adaptive)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("m,slots", [(0, 8), (1, 1), (13, 7), (300, 64),
                                     (100, 1000)])
def test_segment_sum_matches_bincount(m, slots, mode):
    if mode == "interpret" and slots > 64:
        pytest.skip("interpret mode: small shapes only")
    rng = np.random.default_rng(m * 31 + slots)
    ids = rng.integers(-1, slots + 2, m)        # includes out-of-range
    got = segment_sum(ids, slots, mode=mode)
    valid = ids[(ids >= 0) & (ids < slots)]
    assert_array_equal(got, np.bincount(valid, minlength=slots))


@pytest.mark.parametrize("m,slots", [(50_000, 8192), (2048, 700)])
def test_segment_sum_streamed_blocks(m, slots):
    """Id columns spanning several VMEM blocks, slot extents spanning
    several slot tiles (the sketch's (2, 4096) update is 8192 slots)."""
    rng = np.random.default_rng(m + slots)
    ids = rng.integers(-1, slots + 2, m)
    got = segment_sum(ids, slots, mode="interpret")
    valid = ids[(ids >= 0) & (ids < slots)]
    assert_array_equal(got, np.bincount(valid, minlength=slots))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 63), min_size=0, max_size=200),
       st.sampled_from([1, 17, 64]))
def test_segment_sum_property(ids, slots):
    a = np.array(ids, np.int64)
    got = segment_sum(a, slots, mode="xla")
    valid = a[a < slots]
    assert_array_equal(got, np.bincount(valid, minlength=slots))


def _min64_oracle(vals, idx):
    est = vals[0][idx[:, 0]]
    for r in range(1, vals.shape[0]):
        est = np.minimum(est, vals[r][idx[:, r]])
    return est


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("d,w,q", [(1, 1, 1), (2, 50, 33), (4, 100, 64),
                                   (2, 4096, 1024), (3, 70_000, 300)])
def test_gather_min64_reconstructs_f64_min(d, w, q, mode):
    rng = np.random.default_rng(d * 100 + w + q)
    vals = (rng.random((d, w)) * 1e6)           # non-negative f64
    vals[rng.random((d, w)) < 0.2] = 0.0
    idx = rng.integers(0, w, (q, d))
    v = vals.view(np.uint32).reshape(d, w, 2)
    oh, ol = gather_min64(v[..., 1], v[..., 0], idx, mode=mode)
    got = ((oh.astype(np.uint64) << np.uint64(32))
           | ol.astype(np.uint64)).view(np.float64)
    assert_array_equal(got, _min64_oracle(vals, idx))


@settings(max_examples=20, deadline=None)
@given(st.lists(st.floats(0.0, 1e12, allow_nan=False), min_size=2,
                max_size=40))
def test_gather_min64_property(vals_flat):
    w = len(vals_flat) // 2
    vals = np.array(vals_flat[:2 * w], np.float64).reshape(2, w)
    idx = np.stack([np.arange(w), np.arange(w)], axis=1)
    v = vals.view(np.uint32).reshape(2, w, 2)
    oh, ol = gather_min64(v[..., 1], v[..., 0], idx, mode="xla")
    got = ((oh.astype(np.uint64) << np.uint64(32))
           | ol.astype(np.uint64)).view(np.float64)
    assert_array_equal(got, np.minimum(vals[0], vals[1]))
