"""Dispatching wrappers for the segment-reduce ops.

``segment_sum`` pads ids to ``common.column_len`` (masked with -1) and
the slot extent to a pow2 multiple of SLOT_TILE, so both the jit cache
and the Pallas grid see a bounded family of shapes; callers slice the
trimmed counts.  ``gather_min64`` carries float64 sketch state as
(hi, lo) u32 bit-pattern planes — exact for the sketch's non-negative
counters, no x64 mode needed inside the kernels.
"""

from __future__ import annotations

import jax
import numpy as np

from ..common import (QUERY_TILE, SLOT_TILE, U32_MAX, column_len, next_pow2,
                      resolve_mode, round_up)
from .kernel import gather_min64_pallas, segment_sum_pallas
from .ref import gather_min64_ref, segment_sum_ref

_xla_seg = jax.jit(segment_sum_ref, static_argnames=("n_slots",))
_xla_gmin = jax.jit(gather_min64_ref)


def segment_sum(ids, n_slots: int, *, mode=None):
    """Occurrence count per slot for an id column (ids outside
    [0, n_slots) are ignored).  -> numpy (n_slots,) i64."""
    if mode is None:
        mode = resolve_mode(None)
    n_slots = int(n_slots)
    ids = np.asarray(ids)
    if ids.shape[0] == 0 or n_slots == 0:
        return np.zeros(n_slots, np.int64)
    sp = round_up(max(SLOT_TILE, next_pow2(n_slots)), SLOT_TILE)
    ip = np.full(column_len(ids.shape[0]), -1, np.int32)
    ip[:ids.shape[0]] = ids
    if mode == "xla":
        counts = _xla_seg(ip, n_slots=sp)
    else:
        counts = segment_sum_pallas(ip, n_slots=sp,
                                    interpret=(mode == "interpret"))[:, 0]
    return np.asarray(counts)[:n_slots].astype(np.int64)


def gather_min64(hi, lo, idx, *, mode=None):
    """Lexicographic (hi, lo) pair minimum over D one-per-row fetches.

    hi/lo (D, W) u32; idx (Q, D) i32 in [0, W).  -> numpy ((Q,), (Q,))
    u32 — the bit-pattern planes of the float64 count-min estimate."""
    if mode is None:
        mode = resolve_mode(None)
    hi = np.asarray(hi)
    lo = np.asarray(lo)
    idx = np.asarray(idx)
    q = idx.shape[0]
    if q == 0:
        return np.zeros(0, np.uint32), np.zeros(0, np.uint32)
    d, w = hi.shape
    wp = column_len(w)
    qp = round_up(max(QUERY_TILE, next_pow2(q)), QUERY_TILE)
    # pad slots with all-ones (the largest pair) — real idx never lands
    # there, and padded query rows are trimmed anyway
    hp = np.full((d, wp), U32_MAX, np.uint32)
    hp[:, :w] = hi
    lp = np.full((d, wp), U32_MAX, np.uint32)
    lp[:, :w] = lo
    ip = np.zeros((qp, d), np.int32)
    ip[:q] = idx
    if mode == "xla":
        oh, ol = _xla_gmin(hp, lp, ip)
    else:
        oh, ol = gather_min64_pallas(hp, lp, ip,
                                     interpret=(mode == "interpret"))
        oh, ol = oh[:, 0], ol[:, 0]
    return np.asarray(oh)[:q], np.asarray(ol)[:q]
