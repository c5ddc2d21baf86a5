"""Engine-level kernel routing (core/accel.py, DESIGN.md §12).

The byte-parity contract: ``use_kernels`` flips which code executes the
batched hot paths — never what they compute.  Every engine must produce
an identical stats dict and identical lookup results with kernels on and
off, at the default routing threshold and with routing forced onto every
batch (``kernel_min_batch=1``), and the ``kernel_interpret`` mode switch
must not change results either.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import EngineConfig, Store, WriteBatch
from repro.core.engine.config import ENGINES
from repro.obs import Observer

N_KEYS = 2048
VSIZES = np.array([64, 200, 600, 2000, 9000], np.int64)


def run_workload(engine: str, rounds: int = 4, **overrides):
    """Small deterministic mixed workload -> (stats dict, final vid column).

    Mirrors tests/test_refactor_parity.py at reduced scale; the returned
    vids come from one large final ``multi_get`` so value resolution (the
    run_coalesce path) is part of the compared bytes."""
    cfg = EngineConfig.scaled(engine, 8 << 20, est_keys=N_KEYS, **overrides)
    store = Store(cfg)
    rng = np.random.default_rng(99)
    for _ in range(rounds):
        keys = rng.integers(0, N_KEYS, 256).astype(np.uint64)
        sizes = VSIZES[rng.integers(0, len(VSIZES), 256)]
        store.write(WriteBatch().puts(keys, sizes))
        store.write(WriteBatch().deletes(
            rng.integers(0, N_KEYS, 16).astype(np.uint64)))
        store.multi_get(rng.integers(0, N_KEYS, 192).astype(np.uint64))
        store.multi_scan(rng.integers(0, N_KEYS, 4).astype(np.int64), 8)
    store.drain()
    res = store.multi_get(np.arange(N_KEYS, dtype=np.uint64))
    return store.stats(), np.where(res["found"], res["vid"], 0)


@pytest.mark.parametrize("engine", ENGINES)
def test_kernels_on_off_parity_all_engines(engine):
    on_stats, on_vids = run_workload(engine)
    off_stats, off_vids = run_workload(engine, use_kernels=False)
    assert on_stats == off_stats
    np.testing.assert_array_equal(on_vids, off_vids)


def test_kernels_forced_on_every_batch():
    """min_batch=1 routes even the smallest probes through the kernels."""
    on_stats, on_vids = run_workload("scavenger_adaptive", rounds=3,
                                     kernel_min_batch=1)
    off_stats, off_vids = run_workload("scavenger_adaptive", rounds=3,
                                       use_kernels=False)
    assert on_stats == off_stats
    np.testing.assert_array_equal(on_vids, off_vids)


def test_kernel_interpret_mode_parity():
    """The Pallas interpreter computes the same bytes as the auto mode
    (the jitted XLA oracle on CPU; ``kernel_interpret=False`` would force
    compiled Pallas, which needs a TPU).  Tiny workload: interpret mode
    runs the kernel bodies in Python."""
    a = run_workload("scavenger", rounds=1, kernel_interpret=True)
    b = run_workload("scavenger", rounds=1, kernel_interpret=None)
    assert a[0] == b[0]
    np.testing.assert_array_equal(a[1], b[1])


def test_coalesce_window_parity_and_effect():
    """A window must be honored identically by both planners; a 1-record
    window degenerates runs to single records (more random reads)."""
    on = run_workload("scavenger", rounds=2, coalesce_window=2)
    off = run_workload("scavenger", rounds=2, coalesce_window=2,
                       use_kernels=False)
    assert on[0] == off[0]
    unb = run_workload("scavenger", rounds=2)
    assert on[0] != unb[0]      # the window is a real semantic knob


def test_kernel_config_validation():
    with pytest.raises(ValueError, match="kernel_min_batch"):
        EngineConfig(engine="scavenger", kernel_min_batch=0)
    with pytest.raises(ValueError, match="coalesce_window"):
        EngineConfig(engine="scavenger", coalesce_window=0)


def test_kernel_knobs_survive_state_dict_roundtrip():
    cfg = EngineConfig(engine="scavenger", use_kernels=False,
                       kernel_min_batch=7, coalesce_window=3)
    d = cfg.state_dict()
    back = EngineConfig(**d)
    assert (back.use_kernels, back.kernel_min_batch,
            back.coalesce_window) == (False, 7, 3)


def test_kernel_us_histograms_reach_observer():
    """Routed ops emit wall-clock kernel_<opclass>_us histograms through
    the PR 7 observer; unrouted runs emit none."""
    obs = Observer()
    cfg = EngineConfig.scaled("scavenger_adaptive", 8 << 20,
                              est_keys=N_KEYS, observer=obs,
                              kernel_min_batch=1)
    store = Store(cfg)
    rng = np.random.default_rng(7)
    for _ in range(2):
        keys = rng.integers(0, N_KEYS, 256).astype(np.uint64)
        store.write(WriteBatch().puts(
            keys, VSIZES[rng.integers(0, len(VSIZES), 256)]))
        store.multi_get(rng.integers(0, N_KEYS, 192).astype(np.uint64))
    store.drain()
    store.multi_get(np.arange(N_KEYS, dtype=np.uint64))
    for op in ("rank_probe", "interval_rank", "lookup_probe", "run_coalesce",
               "segment_reduce"):
        h = obs.metrics.merged(f"kernel_{op}_us")
        assert h.count > 0, f"no kernel_{op}_us samples"
        assert h.vmax < 60e6        # sanity: wall-clock us, not ns

    off = Observer()
    cfg2 = EngineConfig.scaled("scavenger", 8 << 20, est_keys=N_KEYS,
                               observer=off, use_kernels=False)
    s2 = Store(cfg2)
    s2.write(WriteBatch().puts(np.arange(512, dtype=np.uint64),
                               np.full(512, 200, np.int64)))
    s2.multi_get(np.arange(512, dtype=np.uint64))
    assert off.metrics.merged("kernel_lookup_probe_us").count == 0
