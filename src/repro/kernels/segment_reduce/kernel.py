"""Segment-reduce kernels for the adaptive tracker (DESIGN.md §8, §12).

Two gather/scatter-free primitives cover the DecaySketch / lifetime
histogram hot path:

  * ``segment_sum`` — integer occurrence counts per slot.  TPUs have no
    vector scatter-add, so the grid walks *output* slot tiles and each
    tile one-hot-matches the streamed id column against its slot range
    (compare + reduce, the transpose of the one-hot gather).
    Counts are exact integers; the host applies them to the float64
    sketch state in one vectorized add, which keeps kernel-on and
    kernel-off arithmetic bit-identical.

  * ``gather_min64`` — count-min estimate reads.  The f64 sketch rows
    arrive as (hi, lo) u32 bit-pattern planes (non-negative IEEE doubles
    order lexicographically by bit pattern), fetched one-hot per depth row
    and min-reduced pairwise — bit-exact against numpy's gather + min.

Both stream their per-structure column lane-dense through the grid's
second, "arbitrary" axis (``common.column_spec``) and accumulate into
revisited output blocks; values travel as int32 bit patterns because
Mosaic has no unsigned reductions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import (LANES, QUERY_TILE, SLOT_TILE, STREAM_PARAMS, as_i32,
                      as_u32, column_blocks, column_spec, fetch_block,
                      fold_rows, tile_spec, zero_first)


def _seg_kernel(ids_ref, out_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    zero_first(j, out_ref)
    base = (i * SLOT_TILE
            + jax.lax.broadcasted_iota(jnp.int32, (SLOT_TILE, LANES), 0))

    def step(acc, row, _r):
        return acc + (base == row).astype(jnp.int32)

    acc = fold_rows(ids_ref, jnp.zeros(base.shape, jnp.int32), step)
    out_ref[...] += acc.sum(axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("n_slots", "interpret"))
def segment_sum_pallas(ids, *, n_slots: int, interpret: bool):
    """ids (P,) i32 (-1 = masked), ``common.column_len`` padded; n_slots
    the static output extent (S % SLOT_TILE == 0).  The id column streams
    through the grid's second axis while each slot tile accumulates its
    counts.  -> (S, 1) i32 counts."""
    ids = ids.reshape(-1, LANES)
    s = n_slots
    assert s % SLOT_TILE == 0
    steps = column_blocks(ids.shape[0])[1]
    return pl.pallas_call(
        _seg_kernel,
        grid=(s // SLOT_TILE, steps),
        in_specs=[column_spec(ids.shape[0], steps)],
        out_specs=tile_spec(1, SLOT_TILE),
        out_shape=jax.ShapeDtypeStruct((s, 1), jnp.int32),
        compiler_params=STREAM_PARAMS,
        interpret=interpret,
    )(ids)


def _fetch_kernel(idx_ref, hi_ref, lo_ref, *out_refs, depth: int):
    """Fetch each query's slot per depth row from this block of the
    (D, rows, 128) planes (``common.fetch_block``)."""
    j = pl.program_id(1)
    zero_first(j, *out_refs)
    for d in range(depth):
        idx = idx_ref[:, d:d + 1]                              # (QT, 1)
        out_refs[d][...] += fetch_block(hi_ref.at[d], idx, j)
        out_refs[depth + d][...] += fetch_block(lo_ref.at[d], idx, j)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_min64_pallas(hi, lo, idx, *, interpret: bool):
    """hi/lo (D, W) u32 bit-pattern planes, W ``common.column_len``
    padded; idx (Q, D) i32 slot indices per depth row, Q % QUERY_TILE ==
    0.  The kernel fetches each depth row's (hi, lo) pair as int32 bit
    patterns, streaming the planes through the grid's second axis; the
    unsigned lexicographic min over depth rows runs on the fetched pairs.
    -> ((Q,1), (Q,1)) u32."""
    d, w = hi.shape
    q = idx.shape[0]
    assert q % QUERY_TILE == 0
    rows = w // LANES
    hi, lo = (as_i32(p.reshape(d, rows, LANES)) for p in (hi, lo))
    steps = column_blocks(rows)[1]
    plane = column_spec(rows, steps, lead=(d,))
    got = pl.pallas_call(
        functools.partial(_fetch_kernel, depth=d),
        grid=(q // QUERY_TILE, steps),
        in_specs=[tile_spec(d), plane, plane],
        out_specs=[tile_spec(1)] * (2 * d),
        out_shape=[jax.ShapeDtypeStruct((q, 1), jnp.int32)] * (2 * d),
        compiler_params=STREAM_PARAMS,
        interpret=interpret,
    )(idx, hi, lo)
    best_h, best_l = as_u32(got[0]), as_u32(got[d])
    for r in range(1, d):
        h, low = as_u32(got[r]), as_u32(got[d + r])
        lt = (h < best_h) | ((h == best_h) & (low < best_l))
        best_h = jnp.where(lt, h, best_h)
        best_l = jnp.where(lt, low, best_l)
    return best_h, best_l
