"""Dispatching wrappers for the fused lookup-probe ops.

Padding contract: queries pad to a pow2 multiple of QUERY_TILE (bounds jit
retracing across ragged batch remainders); sorted runs pad with the
``U32_TABLE_PAD`` sentinel and filter words with zeros to a power of two
>= MIN_COLUMN (``common.column_len``), kept as flat columns for the XLA
mode and viewed lane-dense (rows, 128) for the kernels.  Real keys must
stay strictly below the sentinel (u64 keys are accepted when they fit —
the engine's dictionary-encoding contract).

Dispatch-overhead discipline (the CPU roofline in benchmarks/
kernels_bench.py): per-structure operands — the sorted run, the filter
words, the level bounds — are immutable in the engine, so their padded
device copies are cached via ``common.device_cached``; per-batch operands
are padded host-side in NumPy and handed to the jitted callable as-is
(jit ingests NumPy arguments far cheaper than an eager ``jnp.asarray``
round-trip), and outputs are converted whole before trimming so no eager
device slicing runs.

Modes (``repro.kernels.common.resolve_mode``): "xla" jit-compiles the
ref.py oracle on the padded operands, "interpret"/"pallas" run the Pallas
kernel.  All modes are byte-identical on the integer outputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..common import (QUERY_TILE, U32_MAX, U32_TABLE_PAD, column_len,
                      device_cached, next_pow2, resolve_mode, round_up)
from .kernel import count_le_pallas, lookup_probe_pallas, rank_probe_pallas
from .ref import count_le_ref, lookup_probe_ref, rank_probe_ref

_xla_lookup = jax.jit(lookup_probe_ref)
_xla_rank = jax.jit(rank_probe_ref)
_xla_count = jax.jit(count_le_ref)


def _check_u32(a, sorted_run: bool = False) -> np.ndarray:
    """Dictionary-encoding bound check for a key column (sorted runs check
    their last element; query columns scan)."""
    a = np.asarray(a)
    if a.dtype != np.uint32 and a.size:
        top = int(a[-1]) if sorted_run else int(a.max())
        assert top < int(U32_TABLE_PAD), \
            "u64 keys must be dictionary-encoded to u32 for TPU kernels"
    return a


def _pad_q(a, qp) -> np.ndarray:
    out = np.zeros(qp, np.uint32)
    out[:a.shape[0]] = a
    return out


def _run_dev(run: np.ndarray, fill, tag: str, upload=None):
    """Cached padded device copy of an immutable sorted key column."""
    def build():
        n = run.shape[0]
        p = np.full(column_len(n), fill, np.uint32)
        p[:n] = run
        return jnp.asarray(p)
    return device_cached(run, tag, build, upload)


def _words_dev(words: np.ndarray, upload=None):
    """Cached padded device copy of an immutable filter-word column
    (accepts the engine's u64 backing words or raw u32)."""
    def build():
        w = words.view(np.uint32) if words.dtype == np.uint64 \
            else np.asarray(words, np.uint32)
        p = np.zeros(column_len(w.shape[0]), np.uint32)
        p[:w.shape[0]] = w
        return jnp.asarray(p)
    return device_cached(words, "words", build, upload)


def lookup_probe(queries, table_keys, bit_idx, words, *, mode=None,
                 upload=None):
    """Fused bloom + membership/rank probe of one SSTable.

    queries (Q,) and sorted unique table_keys (N,) key columns (u32, or
    u64 that fits); bit_idx (Q, k) u32 pre-modulo'd bloom bit indices;
    words (W,) u32 (or the backing u64) filter words.  -> numpy (may (Q,)
    bool, found (Q,) bool, rank (Q,) i64), rank = searchsorted-left.
    ``upload``: ``common.device_cached``'s scope for a column it uploads
    (here and in ``rank_probe`` / ``interval_rank``)."""
    if mode is None:
        mode = resolve_mode(None)
    queries = _check_u32(queries)
    table_keys = _check_u32(table_keys, sorted_run=True)
    q = queries.shape[0]
    if q == 0:
        return (np.zeros(0, bool), np.zeros(0, bool), np.zeros(0, np.int64))
    k = bit_idx.shape[1]
    qp = round_up(max(QUERY_TILE, next_pow2(q)), QUERY_TILE)
    qs = _pad_q(queries, qp)
    bi = np.zeros((qp, k), np.uint32)
    bi[:q] = bit_idx
    tk = _run_dev(table_keys, U32_TABLE_PAD, "run", upload)
    ws = _words_dev(np.asarray(words), upload)
    if mode == "xla":
        may, found, rank = _xla_lookup(qs, tk, bi, ws)
    else:
        may, found, rank = lookup_probe_pallas(
            qs.reshape(qp, 1), tk, bi, ws, k=k,
            interpret=(mode == "interpret"))
        may, found, rank = may[:, 0], found[:, 0], rank[:, 0]
    return (np.asarray(may)[:q], np.asarray(found)[:q],
            np.asarray(rank)[:q].astype(np.int64))


def rank_probe(queries, table_keys, *, mode=None, upload=None):
    """Membership/rank probe without a filter (memtable snapshots).
    -> numpy (found (Q,) bool, rank (Q,) i64)."""
    if mode is None:
        mode = resolve_mode(None)
    queries = _check_u32(queries)
    table_keys = _check_u32(table_keys, sorted_run=True)
    q = queries.shape[0]
    if q == 0:
        return np.zeros(0, bool), np.zeros(0, np.int64)
    qp = round_up(max(QUERY_TILE, next_pow2(q)), QUERY_TILE)
    qs = _pad_q(queries, qp)
    tk = _run_dev(table_keys, U32_TABLE_PAD, "run", upload)
    if mode == "xla":
        found, rank = _xla_rank(qs, tk)
    else:
        found, rank = rank_probe_pallas(qs.reshape(qp, 1), tk,
                                        interpret=(mode == "interpret"))
        found, rank = found[:, 0], rank[:, 0]
    return (np.asarray(found)[:q],
            np.asarray(rank)[:q].astype(np.int64))


def interval_rank(queries, mins, maxs, *, mode=None, upload=None):
    """Index of the covering [min, max] interval per query; -1 if none.

    ``mins`` sorted ascending, intervals disjoint (an LSM level's file
    bounds).  Matches ``searchsorted(mins, q, 'right') - 1`` plus the max
    bound check.  -> numpy (Q,) i64."""
    if mode is None:
        mode = resolve_mode(None)
    queries = _check_u32(queries)
    mins = _check_u32(mins, sorted_run=True)
    q, n = queries.shape[0], mins.shape[0]
    if q == 0 or n == 0:
        return np.full(q, -1, np.int64)
    qp = round_up(max(QUERY_TILE, next_pow2(q)), QUERY_TILE)
    qs = _pad_q(queries, qp)
    # all-ones pad is > any real query, so padded mins never count as <=
    ms = _run_dev(mins, U32_MAX, "mins", upload)
    if mode == "xla":
        cnt = _xla_count(qs, ms)
    else:
        cnt = count_le_pallas(qs.reshape(qp, 1), ms,
                              interpret=(mode == "interpret"))[:, 0]
    fidx = np.asarray(cnt)[:q].astype(np.int64) - 1
    ok = fidx >= 0
    safe = np.where(ok, fidx, 0)
    ok &= queries.astype(np.uint32) <= maxs[safe].astype(np.uint32)
    return np.where(ok, fidx, -1)
