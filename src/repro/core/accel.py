"""Kernel dispatch for the batched hot paths (DESIGN.md §12).

The read/value/adaptive layers stay written against their NumPy host
implementations; this module routes eligible batches through the jitted
``repro.kernels`` ops instead.  Every routed op is byte-identical to its
host path on the engine's integer columns (and ulp-identical on the
float64 sketch state — see ``kernels/segment_reduce``), so routing is a
pure performance decision: ``EngineConfig.use_kernels`` turns it on,
``kernel_min_batch`` keeps tiny probes on the host where dispatch
overhead would dominate, ``MAX_KERNEL_WORK`` keeps huge ones there (the
kernels compare every query against the whole structure), and
``kernel_interpret`` picks the execution mode
(``kernels.common.resolve_mode``).

Every routed call returns ``None`` when it declines (kernels off, batch
too small or too large for the structure, or keys outside the u32
dictionary-encoding range) — callers fall back to the host path, which
produces the same bytes.  Wall-clock spent inside routed ops is emitted
to the observer as a ``kernel_<op>_us`` histogram named for the kernel
op the call runs (real host microseconds, not simulated time).  A
structure column that is not yet on the device is padded and handed to
it inside that interval, in a ``device.upload`` phase span.
"""

from __future__ import annotations

import contextlib
import functools
import time

import numpy as np

# kernels pad sorted runs with 0xFFFFFFFE: keys must stay strictly below
U32_KEY_LIMIT = np.uint64(0xFFFFFFFE)

# The kernels are brute force: a call costs (batch x structure entries)
# compare lanes, where the host's searchsorted costs O(batch log N).  Past
# this product a call stays on the host — a 1024-key multi_get against a
# full 64 MB kSST (run + k filter words) fits, a GC-Lookup batch of a
# whole vSST does not.  The bound is an estimate from the v5e's vector
# width, not yet a chip measurement.
MAX_KERNEL_WORK = 1 << 33


class KernelPolicy:
    """Resolved per-config routing decision (cached on the config)."""

    __slots__ = ("enabled", "min_batch", "window", "_interpret", "_mode")

    def __init__(self, enabled: bool, min_batch: int = 0, window=None,
                 interpret=None):
        self.enabled = bool(enabled)
        self.min_batch = int(min_batch)
        self.window = window
        self._interpret = interpret
        self._mode = None

    @property
    def mode(self) -> str:
        if self._mode is None:   # lazy: resolving imports jax
            from repro.kernels.common import resolve_mode
            self._mode = resolve_mode(self._interpret)
        return self._mode

    def ready(self, n: int, extent: int = 1) -> bool:
        """Route a batch of ``n`` against a structure of ``extent``
        entries?"""
        return (self.enabled and n >= self.min_batch
                and n * extent <= MAX_KERNEL_WORK)


OFF_POLICY = KernelPolicy(False)


def policy_of(cfg) -> KernelPolicy:
    pol = getattr(cfg, "_kernel_policy", None)
    if pol is None:
        pol = (KernelPolicy(True, cfg.kernel_min_batch,
                            cfg.coalesce_window, cfg.kernel_interpret)
               if cfg.use_kernels else OFF_POLICY)
        cfg._kernel_policy = pol
    return pol


def _fits_u32(*arrays) -> bool:
    """All key columns inside the kernels' u32 dictionary-encoding range
    (sorted columns are checked by their last element upstream)."""
    for a in arrays:
        if len(a) and int(a.max()) >= int(U32_KEY_LIMIT):
            return False
    return True


def _upload(store):
    """The ``upload`` scope a routed call hands the kernel ops: a
    ``device.upload`` phase span, opened only on a device-cache miss."""
    return functools.partial(store.obs.span, store, "device.upload",
                             store.io.lane)


def _emit(store, opclass: str, t0: float) -> None:
    store.obs.on_op(store, f"kernel_{opclass}_us",
                    (time.perf_counter() - t0) * 1e6)


@contextlib.contextmanager
def op_timer(store, opclass: str):
    """Time a fused-op region (host + kernel work) into the observer's
    ``kernel_<opclass>_us`` histogram; no-op while kernels are off."""
    if not policy_of(store.cfg).enabled:
        yield
        return
    t0 = time.perf_counter()
    try:
        yield
    finally:
        _emit(store, opclass, t0)


# ------------------------------------------------------------ read path
def memtable_probe(store, mt, keys):
    """Kernel-routed ``Memtable.get_batch``; None -> host path."""
    pol = policy_of(store.cfg)
    if not pol.ready(len(keys), len(mt)):
        return None
    mk, seqs, ety, vids, vsz, vf = mt.snapshot()
    n = len(mk)
    if n == 0 or int(mk[-1]) >= int(U32_KEY_LIMIT) or not _fits_u32(keys):
        return None
    from repro import kernels
    t0 = time.perf_counter()
    found, rank = kernels.rank_probe(keys, mk, mode=pol.mode,
                                     upload=_upload(store))
    _emit(store, "rank_probe", t0)
    safe = np.where(rank < n, rank, 0)   # host get_batch's gather guard
    return (found, seqs[safe], ety[safe], vids[safe], vsz[safe], vf[safe])


def table_probe(store, t, keys, kraw):
    """Fused bloom + ``SSTable.find`` for one table; None -> host path.

    ``kraw`` is the hoisted (k, Q) u64 ``hash_family`` column slice; the
    modulo to the table's filter size runs on the host (kernels stay in
    u32 lanes) and the resulting bit indices feed the fused probe."""
    pol = policy_of(store.cfg)
    bf = t.bloom
    # the run plus k passes over the filter's u32 words
    if not pol.ready(len(keys), t.n + len(kraw) * 2 * len(bf.bits)):
        return None
    if t.n == 0 or int(t.keys[-1]) >= int(U32_KEY_LIMIT) \
            or not _fits_u32(keys):
        return None
    from repro import kernels
    t0 = time.perf_counter()
    bit_idx = (kraw % np.uint64(bf.nbits)).astype(np.uint32).T   # (Q, k)
    # pass the stable u64 backing words: ops caches the padded device copy
    # against this array's identity (a .view here would defeat the cache)
    may, found, rank = kernels.lookup_probe(keys, t.keys, bit_idx, bf.bits,
                                            mode=pol.mode,
                                            upload=_upload(store))
    _emit(store, "lookup_probe", t0)
    return may, np.where(found, rank, -1)


def assign_files(store, lvl: int, keys):
    """Kernel-routed ``Version.assign_files``; None -> host path."""
    pol = policy_of(store.cfg)
    mins, maxs = store.version.level_bounds(lvl)
    if not pol.ready(len(keys), len(mins)):
        return None
    if (len(mins) == 0 or int(maxs[-1]) >= int(U32_KEY_LIMIT)
            or not _fits_u32(keys)):
        return None
    from repro import kernels
    t0 = time.perf_counter()
    fidx = kernels.interval_rank(keys, mins, maxs, mode=pol.mode,
                                 upload=_upload(store))
    _emit(store, "interval_rank", t0)
    return fidx


# ----------------------------------------------------------- value path
def table_find(store, t, keys):
    """Kernel-routed ``SSTable.find``; None -> host path."""
    pol = policy_of(store.cfg)
    if not pol.ready(len(keys), t.n):
        return None
    if t.n == 0 or int(t.keys[-1]) >= int(U32_KEY_LIMIT) \
            or not _fits_u32(keys):
        return None
    from repro import kernels
    t0 = time.perf_counter()
    found, rank = kernels.rank_probe(keys, t.keys, mode=pol.mode,
                                     upload=_upload(store))
    _emit(store, "rank_probe", t0)
    return np.where(found, rank, -1)


def plan_runs(store, ranks, pos):
    """Kernel-routed fetch planning: sort by (file-rank, position), dedup,
    mark adjacency runs (capped at ``coalesce_window`` kept records when
    configured).  None -> host ``np.unique`` + ``np.split`` planning."""
    pol = policy_of(store.cfg)
    if not pol.ready(len(ranks)):
        return None
    from repro import kernels
    t0 = time.perf_counter()
    out = kernels.run_coalesce(ranks, pos, window=pol.window)
    _emit(store, "run_coalesce", t0)
    return out
