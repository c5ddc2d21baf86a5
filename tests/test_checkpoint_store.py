"""Checkpoint store: durability, GC, quota, pytree round-trips."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointStore, drop_steps, load_pytree,
                              save_pytree, steps_available)


def test_put_get_roundtrip(tmp_path):
    st = CheckpointStore(tmp_path, log_target=4 << 10)
    st.put("a", b"hello")
    st.put("b", b"x" * 5000)
    assert st.get("a") == b"hello"
    assert st.get("b") == b"x" * 5000
    st.close()


def test_overwrite_exposes_garbage_and_gc_reclaims(tmp_path):
    st = CheckpointStore(tmp_path, log_target=2 << 10, gc_threshold=0.2)
    for i in range(20):
        st.put("k", bytes([i]) * 1000)      # same key overwritten
    before = st.total_bytes()
    st.run_gc()
    assert st.total_bytes() < before
    assert st.get("k") == bytes([19]) * 1000
    assert st.gc_runs > 0
    st.close()


def test_lazy_read_gc_reads_only_live(tmp_path):
    st = CheckpointStore(tmp_path, log_target=1 << 10)
    for i in range(10):
        st.put(f"dead{i}", b"d" * 500)
    for i in range(10):
        st.delete(f"dead{i}")
    st.put("live", b"L" * 500)
    read0 = st.gc_read_bytes
    st.run_gc(threshold=0.01)
    gc_read = st.gc_read_bytes - read0
    # far less than the ~5KB of dead data (footers + the one live record)
    assert gc_read < 3000
    assert st.get("live") == b"L" * 500
    st.close()


def test_recovery_after_unclean_shutdown(tmp_path):
    st = CheckpointStore(tmp_path, log_target=1 << 20)
    st.put("x", b"abc" * 100)
    st.put("y", b"def" * 100)
    st.flush()
    # simulate crash: no close/seal
    del st
    st2 = CheckpointStore(tmp_path)
    assert st2.get("x") == b"abc" * 100
    assert st2.get("y") == b"def" * 100
    st2.close()


def test_recovery_truncates_torn_record(tmp_path):
    st = CheckpointStore(tmp_path, log_target=1 << 20)
    st.put("good", b"G" * 100)
    st.flush()
    log = st.open_logs[True]
    # simulate a torn write: garbage appended without manifest entry
    log._fh.write(b"\x01\x02\x03half-a-record")
    log._fh.flush()
    del st
    st2 = CheckpointStore(tmp_path)
    assert st2.get("good") == b"G" * 100
    st2.close()


def test_quota_throttling(tmp_path):
    st = CheckpointStore(tmp_path, quota_bytes=64 << 10,
                         log_target=4 << 10)
    for i in range(50):
        st.put("k", os.urandom(4000))
    assert st.total_bytes() <= (64 << 10) * 1.3
    assert st.throttle_events > 0
    st.close()


def test_hot_cold_separation(tmp_path):
    st = CheckpointStore(tmp_path, log_target=1 << 10)
    st.put("hotk", b"h" * 500, hot=True)
    st.put("coldk", b"c" * 500, hot=False)
    hot_logs = {l.hot for l in st.logs.values()}
    assert hot_logs == {True, False}
    st.close()


def test_pytree_roundtrip_and_retention(tmp_path):
    st = CheckpointStore(tmp_path, log_target=64 << 10)
    tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "nested": {"b": np.ones(5, np.int32)}}
    for step in (1, 2, 3):
        save_pytree(st, "m", step, tree)
    assert steps_available(st, "m") == [1, 2, 3]
    got = load_pytree(st, "m", 3, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree))
    np.testing.assert_array_equal(got["w"], tree["w"])
    np.testing.assert_array_equal(got["nested"]["b"], tree["nested"]["b"])
    drop_steps(st, "m", keep_last=1)
    assert steps_available(st, "m") == [3]
    st.close()


def test_pytree_roundtrip_bfloat16(tmp_path):
    """Full-width model configs keep bfloat16 params, which npy stores as
    raw void records: they must come back bit-identical."""
    st = CheckpointStore(tmp_path)
    w = jax.random.normal(jax.random.key(0), (3, 5)).astype(jnp.bfloat16)
    save_pytree(st, "m", 1, {"w": w})
    got = load_pytree(st, "m", 1, {"w": jax.ShapeDtypeStruct(w.shape,
                                                              w.dtype)})
    assert got["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(got["w"].view(np.uint16),
                                  np.asarray(w).view(np.uint16))
    st.close()


def test_naive_engine_keeps_space_longer(tmp_path):
    def churn(engine):
        root = tmp_path / engine
        st = CheckpointStore(root, engine=engine, log_target=2 << 10)
        for step in range(8):
            st.put("k1", os.urandom(1500))
            st.put("k2", os.urandom(1500))
            st.run_gc()
        amp = st.space_amp()
        st.close()
        return amp
    assert churn("scavenger") <= churn("naive") + 1e-9
