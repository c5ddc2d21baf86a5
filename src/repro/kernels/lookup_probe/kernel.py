"""Fused lookup-probe kernel: bloom bit test + membership/rank in one pass
(the read layer's per-table hot loop, DESIGN.md §12).

TPU layout: the sorted key run, the filter words and a level's file
minimums arrive lane-dense as (rows, 128) columns and stream through VMEM
one block per step of the grid's second, "arbitrary" axis
(``common.column_spec``).  The query tile stays resident and its outputs
are revisited blocks that accumulate across that axis, so no operand is a
whole-structure VMEM block and VMEM use is flat in table size.

Per block, a query tile accumulates elementwise (QT, 128) partial counts
over the block's rows and reduces across lanes once: ``rank`` counts run
entries strictly below the query (exactly ``searchsorted`` left on a
sorted run) and ``eq`` counts equal entries (0/1 on a unique run).  The
bloom word fetch is a one-hot select over the filter block; a query's
word sits in exactly one block and one lane, so the lane reduction is an
int32 sum with one nonzero term, bit-identical to the word.  ``hits``
counts the k hash bits found set (the filter says "maybe" iff all k are).
Filter words and bit indices travel as int32 bit patterns: Mosaic has no
unsigned reductions.  The k bit indices are precomputed on the host from
the engine's hoisted u64 ``hash_family`` column (u64 modulo is host-side
work — kernels stay in 32-bit lanes).
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import (LANES, QUERY_TILE, STREAM_PARAMS, as_i32, column_blocks,
                      column_spec, fetch_block, fold_rows, tile_spec,
                      zero_first)


def _counts(q, blk_ref, cmps):
    """Per-query counts of block entries ``e`` with ``cmp(e, q)``, one
    (QT, 1) int32 column per comparison."""
    qb = jnp.broadcast_to(q, (q.shape[0], LANES))

    def step(accs, row, _r):
        return tuple(a + cmp(row, qb).astype(jnp.int32)
                     for a, cmp in zip(accs, cmps))

    init = tuple(jnp.zeros(qb.shape, jnp.int32) for _ in cmps)
    accs = fold_rows(blk_ref, init, step)
    return tuple(a.sum(axis=1, keepdims=True) for a in accs)


def _bloom_hits(bit_ref, w_ref, j, k: int):
    """Per-query count of the k hash bits set in filter block ``j``."""
    hits = jnp.zeros((bit_ref.shape[0], 1), jnp.int32)
    for h in range(k):
        idx = bit_ref[:, h:h + 1]                         # (QT, 1) int32
        word = fetch_block(w_ref, idx >> 5, j)
        hits = hits + ((word >> (idx & 31)) & 1)
    return hits


def _add_rank(q_ref, tk_ref, eq_ref, rank_ref):
    lt, eq = _counts(q_ref[...], tk_ref, (operator.lt, operator.eq))
    rank_ref[...] += lt
    eq_ref[...] += eq


def _probe_kernel(q_ref, tk_ref, bit_ref, w_ref, hits_ref, eq_ref, rank_ref,
                  *, k: int, run_blocks: int, word_blocks: int):
    j = pl.program_id(1)
    zero_first(j, hits_ref, eq_ref, rank_ref)

    @pl.when(j < run_blocks)
    def _():
        _add_rank(q_ref, tk_ref, eq_ref, rank_ref)

    @pl.when(j < word_blocks)
    def _():
        hits_ref[...] += _bloom_hits(bit_ref, w_ref, j, k)


def _rank_kernel(q_ref, tk_ref, eq_ref, rank_ref):
    zero_first(pl.program_id(1), eq_ref, rank_ref)
    _add_rank(q_ref, tk_ref, eq_ref, rank_ref)


def _count_le_kernel(q_ref, mins_ref, cnt_ref):
    zero_first(pl.program_id(1), cnt_ref)
    (le,) = _counts(q_ref[...], mins_ref, (operator.le,))
    cnt_ref[...] += le


def _col(q: int):
    return jax.ShapeDtypeStruct((q, 1), jnp.int32)


def _lanes(col):
    """Lane-dense (rows, 128) view of a padded flat column."""
    return col.reshape(-1, LANES)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def lookup_probe_pallas(queries, table_keys, bit_idx, words, *, k: int,
                        interpret: bool):
    """queries (Q,1) u32; table_keys (N,) sorted u32 run; bit_idx (Q,k)
    u32; words (W,) u32 filter words.  Q % QUERY_TILE == 0, N and W
    ``common.column_len`` padded.  -> (may, found (Q,1) bool, rank (Q,1)
    i32 = entries strictly below the query)."""
    q = queries.shape[0]
    assert q % QUERY_TILE == 0
    table_keys, words = _lanes(table_keys), as_i32(_lanes(words))
    r, v = table_keys.shape[0], words.shape[0]
    run_blocks, word_blocks = column_blocks(r)[1], column_blocks(v)[1]
    steps = max(run_blocks, word_blocks)
    hits, eq, rank = pl.pallas_call(
        functools.partial(_probe_kernel, k=k, run_blocks=run_blocks,
                          word_blocks=word_blocks),
        grid=(q // QUERY_TILE, steps),
        in_specs=[tile_spec(1), column_spec(r, steps), tile_spec(k),
                  column_spec(v, steps)],
        out_specs=[tile_spec(1)] * 3,
        out_shape=[_col(q)] * 3,
        compiler_params=STREAM_PARAMS,
        interpret=interpret,
    )(queries, table_keys, as_i32(bit_idx), words)
    return hits == k, eq > 0, rank


@functools.partial(jax.jit, static_argnames=("interpret",))
def rank_probe_pallas(queries, table_keys, *, interpret: bool):
    """Membership/rank only (memtable probes carry no bloom filter).
    -> (found (Q,1) bool, rank (Q,1) i32)."""
    q = queries.shape[0]
    assert q % QUERY_TILE == 0
    table_keys = _lanes(table_keys)
    r = table_keys.shape[0]
    steps = column_blocks(r)[1]
    eq, rank = pl.pallas_call(
        _rank_kernel,
        grid=(q // QUERY_TILE, steps),
        in_specs=[tile_spec(1), column_spec(r, steps)],
        out_specs=[tile_spec(1)] * 2,
        out_shape=[_col(q)] * 2,
        compiler_params=STREAM_PARAMS,
        interpret=interpret,
    )(queries, table_keys)
    return eq > 0, rank


@functools.partial(jax.jit, static_argnames=("interpret",))
def count_le_pallas(queries, mins, *, interpret: bool):
    """Per-query count of run entries <= query (level file assignment).
    mins (N,) u32, ``common.column_len`` padded.  -> (Q,1) i32."""
    q = queries.shape[0]
    assert q % QUERY_TILE == 0
    mins = _lanes(mins)
    r = mins.shape[0]
    steps = column_blocks(r)[1]
    return pl.pallas_call(
        _count_le_kernel,
        grid=(q // QUERY_TILE, steps),
        in_specs=[tile_spec(1), column_spec(r, steps)],
        out_specs=tile_spec(1),
        out_shape=_col(q),
        compiler_params=STREAM_PARAMS,
        interpret=interpret,
    )(queries, mins)
