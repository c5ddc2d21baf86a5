"""TPU Pallas kernels for the engine's compute hot spots.

Validated in interpret mode on CPU against the pure-jnp oracles in each
package's ref.py; lowered with explicit BlockSpec VMEM tiling for TPU.
``run_coalesce`` is a jitted jnp graph with no Pallas kernel.
The engine routes its batched hot paths here through ``core/accel.py``
(``EngineConfig.use_kernels``, DESIGN.md §12).
"""

from .bloom import bloom_build, bloom_probe, bloom_build_ref, bloom_probe_ref
from .gc_lookup import gc_lookup, gc_lookup_ref
from .lookup_probe import (interval_rank, lookup_probe, lookup_probe_ref,
                           rank_probe, rank_probe_ref)
from .merge import merge_dedup, merge_dedup_ref
from .partition import hot_cold_partition, hot_cold_partition_ref
from .paged_gather import page_gather, page_gather_ref
from .run_coalesce import run_coalesce
from .segment_reduce import (gather_min64, gather_min64_ref, segment_sum,
                             segment_sum_ref)

__all__ = [
    "bloom_build", "bloom_probe", "bloom_build_ref", "bloom_probe_ref",
    "gc_lookup", "gc_lookup_ref", "merge_dedup", "merge_dedup_ref",
    "hot_cold_partition", "hot_cold_partition_ref",
    "page_gather", "page_gather_ref",
    "lookup_probe", "lookup_probe_ref", "rank_probe", "rank_probe_ref",
    "interval_rank", "run_coalesce",
    "segment_sum", "segment_sum_ref", "gather_min64", "gather_min64_ref",
]
