"""Compile the main-path kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler that ships with jaxlib compiles for a
described ``v5e:2x2`` topology, which refuses what the chip would refuse —
unaligned slices, unsupported layouts, unsigned reductions, more VMEM than
a kernel may use.  Shapes are the deployment ones: a 64 MB kSST at the
paper's defaults holds ~1.2 M entries (a 2^21-entry padded run, ~2^19
filter words at 10 bits/key), multi_get batches of 1024 and 4096 keys, and
the adaptive tracker's (2, 4096) count-min sketch.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers that decide
at import whether these tests exist would collect different tests.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lookup_probe.kernel import (count_le_pallas,
                                               lookup_probe_pallas,
                                               rank_probe_pallas)
from repro.kernels.run_coalesce import coalesce_graph
from repro.kernels.segment_reduce.kernel import (gather_min64_pallas,
                                                 segment_sum_pallas)

RUN = 1 << 21           # padded sorted run of a full 64 MB kSST
WORDS = 1 << 19         # padded u32 filter words at 10 bits/key
K = 7                   # bloom probes at 10 bits/key
SKETCH = (2, 4096)      # adaptive_sketch_depth x adaptive_sketch_width


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return fn.lower(*args, **static).compile().as_text()


@pytest.mark.parametrize("q", [1024, 4096])
def test_lookup_probe_compiles(q, one_chip, no_cache):
    hlo = _compile(lookup_probe_pallas, one_chip,
                   ((q, 1), jnp.uint32), ((RUN,), jnp.uint32),
                   ((q, K), jnp.uint32), ((WORDS,), jnp.uint32),
                   k=K, interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("q", [1024, 4096])
def test_rank_probe_compiles(q, one_chip, no_cache):
    hlo = _compile(rank_probe_pallas, one_chip,
                   ((q, 1), jnp.uint32), ((RUN,), jnp.uint32),
                   interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("q", [1024, 4096])
def test_interval_rank_compiles(q, one_chip, no_cache):
    hlo = _compile(count_le_pallas, one_chip,
                   ((q, 1), jnp.uint32), ((RUN,), jnp.uint32),
                   interpret=False)
    assert "tpu_custom_call" in hlo


def test_segment_sum_compiles(one_chip, no_cache):
    d, w = SKETCH
    hlo = _compile(segment_sum_pallas, one_chip,
                   ((d * 1024,), jnp.int32), n_slots=d * w, interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("q", [1024, 4096])
def test_gather_min64_compiles(q, one_chip, no_cache):
    d, w = SKETCH
    hlo = _compile(gather_min64_pallas, one_chip,
                   ((d, w), jnp.uint32), ((d, w), jnp.uint32),
                   ((q, d), jnp.int32), interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("window", [None, 16])
def test_run_coalesce_graph_compiles(window, one_chip, no_cache):
    """run_coalesce has no Pallas kernel: its jitted graph lowers to XLA's
    own sort on the TPU."""
    hlo = _compile(coalesce_graph, one_chip,
                   ((4096,), jnp.uint32), ((4096,), jnp.uint32),
                   window=window)
    assert "tpu_custom_call" not in hlo
    assert "sort" in hlo
