"""Kernel microbenchmarks + the hot-path roofline (DESIGN.md §12).

Two sections:

  * legacy per-kernel rows — Pallas (interpret) vs jnp oracle, correctness
    timing on CPU; TPU wall-time comes from real hardware, not this
    container;
  * ``kernels/roofline/*`` — the three fused hot-path ops the engine
    routes through ``core/accel.py`` (lookup_probe / run_coalesce /
    segment_reduce), measured host vs jitted (``resolve_mode`` default:
    the XLA oracle on CPU, compiled Pallas on TPU) at batch 256 / 1024 /
    4096.  The lookup row drives the *real* code both ways — the engine's
    ``BloomFilter.may_contain`` + ``SSTable.find`` host functions against
    the routed ``accel.table_probe`` — on a real flushed table, so the
    row prices everything the dispatch actually pays (padding, device
    residency, output conversion) against everything the host actually
    pays (mask copies, dtype guards, where-passes).  ``us_op`` rows trace
    where the dispatch-overhead/throughput crossover sits — the basis
    for ``EngineConfig.kernel_min_batch``.

Every run is appended to the repo-root ``BENCH_kernels.json`` trajectory
(``benchmarks.common.persist_trajectory``) so the roofline accumulates
across sessions.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EngineConfig, Store, WriteBatch, accel
from repro.core.engine.keys import BloomFilter, hash_family
from repro.core.values.fetch import plan_runs_host
from repro.kernels import (bloom_build, bloom_probe, bloom_probe_ref,
                           gc_lookup, gc_lookup_ref, hot_cold_partition,
                           merge_dedup, page_gather, page_gather_ref,
                           run_coalesce, segment_sum)

from .common import persist_trajectory, row, trajectory_path

TRAJECTORY = "BENCH_kernels.json"

ROOFLINE_BATCHES = (256, 1024, 4096)
_TABLE_N = 65536            # sorted-run length for the lookup roofline
_N_FILES = 8                # vSSTs in the coalesce roofline
_DEPTH, _WIDTH = 2, 4096    # DecaySketch shape for the segment roofline


def _time(fn, *args, reps=3):
    fn(*args)                      # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _wall(fn, reps=20):
    """Best-of-reps wall-clock microseconds for a host-or-dispatch thunk
    (min filters scheduler noise; both sides get the same treatment)."""
    fn(), fn()                     # warm caches / jit
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


# ------------------------------------------------------------- roofline
def _roofline_lookup(rng, rows):
    """Fused bloom + membership/rank probe: the engine's real host
    functions vs the routed ``accel.table_probe``, on a real table.

    Hash-family hoisting (read/lookup.py) is shared by both paths, so
    ``kraw`` is precomputed outside the timed region exactly as the
    engine does."""
    cfg = EngineConfig.scaled("scavenger", 64 << 20, est_keys=_TABLE_N)
    store = Store(cfg)
    keys = np.arange(1, 8 * _TABLE_N, 8, dtype=np.uint64)[:_TABLE_N]
    store.write(WriteBatch().puts(keys, np.full(_TABLE_N, 200, np.int64)))
    store.drain()
    t = max((t for lvl in store.version.levels for t in lvl),
            key=lambda t: t.n)
    k = BloomFilter.k_for(cfg.filter_bits_per_key)

    def host(queries, kraw):
        may = t.bloom.may_contain(queries, raw=kraw)
        return may, t.find(queries[may])

    for q in ROOFLINE_BATCHES:
        queries = rng.choice(t.keys, q)
        kraw = hash_family(queries, k)
        assert accel.table_probe(store, t, queries, kraw) is not None
        host_us = _wall(lambda: host(queries, kraw))
        jit_us = _wall(lambda: accel.table_probe(store, t, queries, kraw))
        rows.append(row(f"kernels/roofline/lookup_probe/b{q}", jit_us,
                        host_us=host_us, us_op=jit_us / q,
                        host_us_op=host_us / q, speedup=host_us / jit_us,
                        batch=q, n=t.n))


def _roofline_coalesce(rng, rows):
    """Run planning on the device vs the NumPy host planner."""
    for m in ROOFLINE_BATCHES:
        rank = np.sort(rng.integers(0, _N_FILES, m))
        pos = rng.integers(0, m // 2, m)

        def host():
            return plan_runs_host(rank, pos, 16)

        jit_us = _wall(lambda: run_coalesce(rank, pos, window=16))
        host_us = _wall(host)
        rows.append(row(f"kernels/roofline/run_coalesce/b{m}", jit_us,
                        host_us=host_us, us_op=jit_us / m,
                        host_us_op=host_us / m, speedup=host_us / jit_us,
                        batch=m, files=_N_FILES))


def _roofline_segment(rng, rows):
    """Sketch-row increments vs the per-row bincount host update."""
    shift = np.arange(_DEPTH)[:, None] * _WIDTH
    for m in ROOFLINE_BATCHES:
        idx = rng.integers(0, _WIDTH, (_DEPTH, m))
        counts = np.zeros((_DEPTH, _WIDTH))

        def host():
            c = counts.copy()
            for r in range(_DEPTH):
                c[r] += np.bincount(idx[r], minlength=_WIDTH)
            return c

        def jitted():
            seg = segment_sum((idx + shift).ravel(), _DEPTH * _WIDTH)
            return counts + seg.reshape(_DEPTH, _WIDTH)

        jit_us = _wall(jitted)
        host_us = _wall(host)
        rows.append(row(f"kernels/roofline/segment_reduce/b{m}", jit_us,
                        host_us=host_us, us_op=jit_us / m,
                        host_us_op=host_us / m, speedup=host_us / jit_us,
                        batch=m, depth=_DEPTH, width=_WIDTH))


def run(scale=None):
    rng = np.random.default_rng(0)
    rows = []
    n, q = 8192, 1024
    skeys = np.sort(rng.choice(np.arange(10 * n, dtype=np.uint32), n,
                               replace=False))
    svids = skeys + 1
    svf = skeys % 997
    queries = rng.choice(skeys, q)
    us_k = _time(lambda: gc_lookup(queries, skeys, svids, svf))
    us_r = _time(lambda: gc_lookup_ref(jnp.asarray(queries),
                                       jnp.asarray(skeys),
                                       jnp.asarray(svids),
                                       jnp.asarray(svf)))
    rows.append(row("kernels/gc_lookup", us_k, ref_us=us_r, n=n, q=q))

    words, k, nbits = bloom_build(skeys)
    us_k = _time(lambda: bloom_probe(queries, words, k, nbits))
    us_r = _time(lambda: bloom_probe_ref(jnp.asarray(queries), words, k,
                                         nbits))
    rows.append(row("kernels/bloom_probe", us_k, ref_us=us_r, q=q))

    ak = np.sort(rng.choice(np.arange(1 << 20, dtype=np.uint32), 2048,
                            replace=False))
    us_k = _time(lambda: merge_dedup(ak, ak, ak, ak, ak, ak))
    rows.append(row("kernels/merge_dedup", us_k, n=4096))

    hot = rng.random(4096) < 0.3
    us_k = _time(lambda: hot_cold_partition(
        ak.repeat(2)[:4096], hot, ak.repeat(2)[:4096],
        np.full(4096, 100, np.uint32)))
    rows.append(row("kernels/partition", us_k, n=4096))

    pages = jnp.asarray(rng.standard_normal((256, 16, 128)),
                        jnp.float32)
    table = rng.integers(0, 256, (8, 32)).astype(np.int32)
    us_k = _time(lambda: page_gather(table, pages))
    us_r = _time(lambda: page_gather_ref(jnp.asarray(table), pages))
    rows.append(row("kernels/page_gather", us_k, ref_us=us_r))

    _roofline_lookup(rng, rows)
    _roofline_coalesce(rng, rows)
    _roofline_segment(rng, rows)
    # the routing rationale: past the crossover, jitted must win
    for r in rows:
        if r["name"].startswith("kernels/roofline/lookup_probe/b"):
            q = int(r["name"].rsplit("/b", 1)[1])
            if q >= 1024:
                assert "speedup=" in r["derived"], r
                sp = float(r["derived"].split("speedup=")[1].split()[0])
                assert sp > 1.0, f"jitted lookup slower than host: {r}"
    persist_trajectory("kernels", rows,
                       path=os.environ.get("REPRO_BENCH_TRAJECTORY",
                                           trajectory_path(TRAJECTORY)))
    return rows
