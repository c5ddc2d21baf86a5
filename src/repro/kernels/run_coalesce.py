"""Run-coalescing op: sort + dedup + adjacency-run planning for the
value-fetch path (paper §III-B.1, DESIGN.md §12).

The fetch planner turns a column of (file-rank, record-position) pairs
into I/O runs: sort lexicographically, drop duplicate pairs, and start a
new run at every file change or position gap > 1 — plus every ``window``
kept records when a coalesce window caps run length (qd-style bounded
requests).

One jitted jnp graph on every platform: a stable two-pass argsort, shifted
compares, and cumulative scans, which XLA lowers to its own sort on the
TPU.  There is no Pallas kernel: a gather-free bitonic network needs
(rows, 2, stride) reshapes that Mosaic cannot lay out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import U32_MAX, next_pow2


@functools.partial(jax.jit, static_argnames=("window",))
def coalesce_graph(rank, pos, window=None):
    """rank/pos (M,) u32 -> (rank_s, pos_s, keep, run_start), sorted by
    the lexicographic (rank, pos) pair."""
    o1 = jnp.argsort(pos, stable=True)
    o2 = jnp.argsort(rank[o1], stable=True)
    order = o1[o2]
    r, p = rank[order], pos[order]
    m = r.shape[0]
    i0 = jnp.arange(m) == 0
    prev_r = jnp.concatenate([jnp.zeros((1,), r.dtype), r[:-1]])
    prev_p = jnp.concatenate([jnp.zeros((1,), p.dtype), p[:-1]])
    keep = i0 | (r != prev_r) | (p != prev_p)
    start = (i0 | (r != prev_r) | (p - prev_p > jnp.uint32(1))) & keep
    if window is not None:
        kept = jnp.cumsum(keep.astype(jnp.int32))
        base = jax.lax.cummax(jnp.where(start, kept, 0))
        start = start | (keep & ((kept - base) % window == 0))
    return r, p, keep, start


def run_coalesce(rank, pos, *, window=None):
    """Plan coalesced I/O runs for (file-rank, record-position) pairs.

    -> numpy (rank_s i64, pos_s i64, keep bool, run_start bool), all (M,)
    sorted by (rank, pos); duplicates have keep False, and run_start marks
    the first kept record of each adjacent run (capped at ``window`` kept
    records per run when set).  The column pads to a power of two with
    all-ones sentinels (they sort after every real pair; real ranks and
    positions must stay below them) and is trimmed back."""
    rank = np.asarray(rank)
    pos = np.asarray(pos)
    m = rank.shape[0]
    if m == 0:
        e = np.zeros(0, np.int64)
        return e, e.copy(), np.zeros(0, bool), np.zeros(0, bool)
    assert int(rank.max()) < int(U32_MAX) and int(pos.max()) < int(U32_MAX)
    if window is not None:
        window = int(window)
        assert window >= 1
    mp = max(2, next_pow2(m))
    rp = np.full(mp, U32_MAX, np.uint32)
    rp[:m] = rank
    pp = np.full(mp, U32_MAX, np.uint32)
    pp[:m] = pos
    r, p, keep, start = coalesce_graph(rp, pp, window=window)
    return (np.asarray(r)[:m].astype(np.int64),
            np.asarray(p)[:m].astype(np.int64),
            np.asarray(keep)[:m], np.asarray(start)[:m])
