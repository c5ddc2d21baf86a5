"""Benchmark harness entrypoint — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  REPRO_BENCH_SCALE=quick|full.
Select modules: python -m benchmarks.run [--list] [--shards N]
[--shard-policy {hash,range}] [module ...]
"""

from __future__ import annotations

import argparse
import ast
import os
import time
import traceback

MODULES = [
    "fig02_tradeoff", "fig03_gc_breakdown", "fig05_spaceamp_sources",
    "fig12_micro", "fig13_ycsb", "fig14_nolimit", "fig16_features",
    "fig17_ablation_space", "fig19_workloads", "fig20_space_limits",
    "table1_space_overhead", "batch_api", "read_path", "sharding",
    "adaptive_gc", "recovery", "elasticity", "kernels_bench",
    "serving_cache", "checkpoint_store", "roofline",
]


def describe(name: str) -> str:
    """First docstring line of a benchmark module (AST parse: listing must
    not import heavyweight dependencies like jax)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"{name}.py")
    try:
        with open(path) as f:
            doc = ast.get_docstring(ast.parse(f.read())) or ""
    except (OSError, SyntaxError):
        return "(no description)"
    return doc.strip().splitlines()[0] if doc.strip() else "(no description)"


def list_modules() -> None:
    width = max(len(n) for n in MODULES)
    try:
        for name in MODULES:
            print(f"{name:<{width}}  {describe(name)}")
    except BrokenPipeError:            # `--list | head` closed the pipe
        os._exit(0)


def main() -> None:
    import importlib
    ap = argparse.ArgumentParser()
    ap.add_argument("modules", nargs="*", default=None)
    ap.add_argument("--list", action="store_true",
                    help="print registered benchmark modules with one-line "
                         "descriptions and exit")
    ap.add_argument("--shards", type=int, default=None,
                    help="run workloads against a ShardedStore of N shards")
    ap.add_argument("--shard-policy", choices=("hash", "range"),
                    default=None)
    ap.add_argument("--trace", metavar="DIR", default=None,
                    help="attach an observer to every store and dump one "
                         "observability directory per module under DIR "
                         "(events/metrics/health + Chrome trace JSON; see "
                         "python -m repro.obs)")
    args = ap.parse_args()
    if args.list:
        list_modules()
        return
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    if args.shards is not None:
        os.environ["REPRO_SHARDS"] = str(args.shards)
    if args.shard_policy is not None:
        os.environ["REPRO_SHARD_POLICY"] = args.shard_policy
    if args.trace is not None:
        os.environ["REPRO_TRACE_DIR"] = args.trace
    names = args.modules or MODULES
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            for r in mod.run():
                print(f"{r['name']},{r['us_per_call']},{r['derived']}",
                      flush=True)
            from benchmarks import common
            out = common.dump_trace(name)
            if out is not None:
                print(f"# {name} trace -> {out}", flush=True)
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception as e:
            failures += 1
            print(f"# {name} FAILED: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
