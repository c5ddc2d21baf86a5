"""The probe family's byte count and the peaks table: one count gives a
share of at most 100% for both the brute-force kernel and the
``searchsorted`` graph timing the same call."""

import time

import numpy as np
import pytest

from chipbench.work import least_bytes, peaks_for


def _time(fn, reps=3):
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


@pytest.mark.parametrize("kind", ["lookup", "rank", "count_le"])
def test_share_at_most_100_for_both_implementations(kind):
    from repro import kernels
    rng = np.random.default_rng(0)
    run = np.sort(rng.choice(1 << 20, 4096, replace=False)).astype(np.uint32)
    q = rng.integers(0, 1 << 20, 256).astype(np.uint32)
    bits = rng.integers(0, 1 << 14, (256, 7)).astype(np.uint32)
    words = rng.integers(0, 1 << 32, 1 << 9, dtype=np.uint64).astype(
        np.uint32)

    def call(mode):
        if kind == "lookup":
            return lambda: kernels.lookup_probe(q, run, bits, words,
                                                mode=mode)
        if kind == "rank":
            return lambda: kernels.rank_probe(q, run, mode=mode)
        return lambda: kernels.interval_rank(q, run, run + 1, mode=mode)

    hbm = peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    least = least_bytes(kind, 256) / hbm
    for mode in ("interpret", "xla"):     # brute force, searchsorted
        share = least / _time(call(mode))
        assert 0 < share <= 1.0, (kind, mode, share)


def test_count_grows_with_queries_and_has_a_floor():
    assert least_bytes("rank", 1) == least_bytes("rank", 256)
    assert least_bytes("lookup", 512) == 2 * least_bytes("lookup", 256)
    assert least_bytes("lookup", 256, k=7) > least_bytes("rank", 256)
    with pytest.raises(ValueError):
        least_bytes("sort")


def test_unknown_device_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")
