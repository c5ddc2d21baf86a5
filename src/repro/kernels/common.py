"""Shared helpers for the TPU kernels.

TPU adaptation notes (DESIGN.md §3): the engine's u64 keys enter kernels as
32-bit lanes (the workloads' key spaces are dense ints < 2^32; 24B string
keys would be dictionary-encoded to u32 at the table level).  TPU vector
units have no efficient per-lane gather from VMEM, so every kernel is built
from gather-free primitives:

  * membership/rank  -> tiled brute-force compare-and-reduce (no pointer
    chasing; at a 1.2 M-entry run on a v5e it measured slower per call
    than XLA's searchsorted graph — PERF.md),
  * bloom word fetch -> one-hot select-reduce,
  * merge/sort       -> bitonic compare-exchange networks at fixed strides,
  * page fetch       -> block-level dynamic slices driven by scalar-prefetch
    (the one dynamic-indexing form TPUs do support).
"""

from __future__ import annotations

import contextlib
import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MIX1 = np.uint32(0x85EBCA6B)
MIX2 = np.uint32(0xC2B2AE35)

# ---- canonical VMEM tile sizes (the only place magic tiles may live;
# enforced by the config-discipline scavlint pass) ----
QUERY_TILE = 256        # query rows per grid step (sublane-friendly)
SLOT_TILE = 256         # output slots per segment-reduce grid step
LANES = 128             # streamed columns are lane-dense (rows, LANES)
LANE_BITS = 7           # log2(LANES)
SUBLANES = 8            # rows per (8, 128) u32 vreg tile
MIN_COLUMN = SUBLANES * LANES   # smallest padded streamed column
BLOCK_ROWS = 256        # max rows per streamed VMEM block (128 KiB u32)
TABLE_CHUNK = 512       # 1-D chunks of the unrouted bloom/gc_lookup kernels
WORD_CHUNK = 512

# u32 lane sentinels: queries pad with MAX, table runs with MAX-1, so real
# keys must stay strictly below MAX-1 (checked by the ops wrappers)
U32_MAX = np.uint32(0xFFFFFFFF)
U32_TABLE_PAD = np.uint32(0xFFFFFFFE)


def interpret_default() -> bool:
    """Run kernels in interpret mode unless on a real TPU."""
    return jax.default_backend() != "tpu"


# ---- device residency cache for immutable host columns ----
# Host->device transfer dominates CPU dispatch for the big per-structure
# operands (sorted runs, filter words).  The engine's table columns are
# immutable, so their padded device copies are cached against the host
# array's identity and dropped when the host column is garbage collected
# (table eviction / version turnover).
_DEVICE_CACHE: dict = {}


def device_cached(host_arr: np.ndarray, tag: str, build, upload=None):
    """``build()``'s device array, cached under ``(id(host_arr), tag)``.

    The host array must be treated as immutable by the caller — the cache
    returns the stale device copy otherwise.  ``upload``, where given, is
    called on a miss for the context manager the build runs in (the
    caller's scope for a column handed to the device)."""
    key = (id(host_arr), tag)
    ent = _DEVICE_CACHE.get(key)
    if ent is not None and ent[0]() is host_arr:
        return ent[1]
    with (upload or contextlib.nullcontext)():
        dev = build()
    _DEVICE_CACHE[key] = (weakref.ref(host_arr), dev)
    weakref.finalize(host_arr, _DEVICE_CACHE.pop, key, None)
    return dev


def resolve_mode(kernel_interpret: bool | None) -> str:
    """Map ``EngineConfig.kernel_interpret`` to an execution mode.

    ``None``  -> "pallas" (compiled Mosaic) on a real TPU, "xla" (the
                 jit-compiled pure-jnp oracle graph — same integer math,
                 no interpreter overhead) everywhere else;
    ``True``  -> "interpret" (the Pallas interpreter, for kernel-fidelity
                 runs on CPU);
    ``False`` -> "pallas" (force compiled lowering).

    All three modes are byte-identical on the engine's integer columns —
    the mode only moves where the arithmetic runs.
    """
    if kernel_interpret is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return "interpret" if kernel_interpret else "pallas"


# ---- streamed columns (the routed kernels, DESIGN.md §12) ----
# A per-structure column (sorted run, filter words, sketch row) is padded
# to a power of two >= MIN_COLUMN and viewed lane-dense as (rows, LANES).
# It streams through VMEM one (block_rows, LANES) block per step of the
# grid's second, "arbitrary" axis, so VMEM use is flat in structure size.
STREAM_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def column_len(n: int) -> int:
    """Padded length of a streamed column of ``n`` entries."""
    return max(MIN_COLUMN, next_pow2(n))


def column_blocks(rows: int) -> tuple[int, int]:
    """(block_rows, n_blocks) of a (rows, LANES) column."""
    br = min(BLOCK_ROWS, rows)
    return br, rows // br


def column_spec(rows: int, steps: int, lead: tuple = ()) -> pl.BlockSpec:
    """BlockSpec streaming a ``lead + (rows, LANES)`` column along grid
    axis 1.  Steps past the column's last block revisit it (no new DMA);
    kernels skip their work there with ``pl.when``."""
    br, nb = column_blocks(rows)
    zeros = (0,) * len(lead)
    return pl.BlockSpec(lead + (br, LANES),
                        lambda i, j: zeros + (jnp.minimum(j, nb - 1), 0))


def tile_spec(width: int, tile: int = QUERY_TILE) -> pl.BlockSpec:
    """BlockSpec of a (tile, width) row tile, resident across grid axis 1
    (outputs accumulate in it: a revisited block)."""
    return pl.BlockSpec((tile, width), lambda i, j: (i, 0))


def zero_first(j, *outs):
    """Zero revisited output blocks at the first step of grid axis 1."""
    @pl.when(j == 0)
    def _():
        for o in outs:
            o[...] = jnp.zeros(o.shape, o.dtype)


def as_i32(a):
    """int32 bit pattern of a u32 array (Mosaic reduces no unsigned)."""
    return jax.lax.bitcast_convert_type(a, jnp.int32)


def as_u32(a):
    return jax.lax.bitcast_convert_type(a, jnp.uint32)


def fold_rows(blk_ref, init, step):
    """Fold ``step(acc, row, r)`` over the rows of a (rows, LANES) block,
    one (SUBLANES, LANES) tile load per loop trip; ``row`` is (1, LANES)
    and ``r`` its row index in the block."""
    def body(t, acc):
        r0 = pl.multiple_of(t * SUBLANES, SUBLANES)
        tile = blk_ref[pl.ds(r0, SUBLANES), :]
        for s in range(SUBLANES):
            acc = step(acc, tile[s:s + 1, :], r0 + s)
        return acc
    return jax.lax.fori_loop(0, blk_ref.shape[0] // SUBLANES, body, init)


def fetch_block(blk_ref, idx, j):
    """Per-query value at flat column index ``idx`` (QT, 1) when it falls
    in block ``j`` of a streamed column, else 0: a one-hot select over the
    block's rows, then a lane reduction that sums one nonzero term, so the
    bits come back unchanged."""
    row = (idx >> LANE_BITS) - j * blk_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def step(acc, vals, r):
        return jnp.where(row == r, vals, acc)

    acc = fold_rows(blk_ref, jnp.zeros((idx.shape[0], LANES), jnp.int32),
                    step)
    return jnp.where(lane == (idx & (LANES - 1)), acc, 0).sum(
        axis=1, keepdims=True)


def mix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer (u32 -> u32), vectorized."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * MIX1
    x = x ^ (x >> 13)
    x = x * MIX2
    return x ^ (x >> 16)


def pad_to(x: jnp.ndarray, n: int, fill) -> jnp.ndarray:
    if x.shape[0] == n:
        return x
    pad = jnp.full((n - x.shape[0],) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, pad], axis=0)


def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def bitonic_merge(keys, *payloads, ascending=True):
    """Merge a bitonic sequence of length 2^k (log fixed-stride passes)."""
    n = keys.shape[0]
    assert (n & (n - 1)) == 0, "power-of-two length required"
    stride = n // 2
    while stride >= 1:
        rows = n // (2 * stride)
        dir_up = jnp.full((rows,), ascending)
        keys, payloads = _cmpx(keys, payloads, stride, dir_up)
        stride //= 2
    return (keys,) + payloads


def _cmpx(keys, payloads, stride, dir_up_row):
    """One compare-exchange pass at fixed ``stride`` (gather-free:
    reshape to (rows, 2, stride) and swap halves).  ``dir_up_row`` is a
    (rows,) bool: ascending rows swap when lo > hi."""
    n = keys.shape[0]
    k2 = keys.reshape(-1, 2, stride)
    lo, hi = k2[:, 0, :], k2[:, 1, :]
    up = dir_up_row[:, None]
    swap = jnp.where(up, lo > hi, lo < hi)
    keys = jnp.stack([jnp.where(swap, hi, lo), jnp.where(swap, lo, hi)],
                     axis=1).reshape(n)
    out_p = []
    for p in payloads:
        p2 = p.reshape(-1, 2, stride)
        plo, phi = p2[:, 0, :], p2[:, 1, :]
        out_p.append(jnp.stack([jnp.where(swap, phi, plo),
                                jnp.where(swap, plo, phi)],
                               axis=1).reshape(n))
    return keys, tuple(out_p)


def bitonic_sort(keys, *payloads, ascending=True):
    """Full bitonic sort network (log^2 fixed-stride passes, gather-free)."""
    n = keys.shape[0]
    assert (n & (n - 1)) == 0
    size = 2
    while size <= n:
        stride = size // 2
        while stride >= 1:
            rows = n // (2 * stride)
            row_base = jnp.arange(rows) * (2 * stride)
            dir_up = ((row_base & size) == 0) == ascending
            keys, payloads = _cmpx(keys, payloads, stride, dir_up)
            stride //= 2
        size *= 2
    return (keys,) + payloads
