"""§Perf report: baseline vs optimized cells, from dry-run artifacts.

  PYTHONPATH=src:. python -m benchmarks.perf_report
  PYTHONPATH=src:. python -m benchmarks.perf_report --gate [--gate-tol X]

Besides printing the markdown table, the report appends its rows to the
repo-root ``BENCH_adaptive.json`` trajectory file (``common.
persist_trajectory``) so perf history survives across runs.

A second table reports tail latency: p50/p95/p99 per op-class histogram
from the observability metrics registry (DESIGN.md §11), measured on a
fresh observed load+update run per engine and persisted to the
``BENCH_obs.json`` trajectory.

``--gate`` is the perf **regression gate** (run by ``make bench-smoke``
and CI): it compares the newest entry of every trajectory section in the
``BENCH_*.json`` files against the median of its trailing window — same
section, same bench scale — and exits nonzero when a tracked metric
regressed past the tolerance.  It only reads trajectory files (no dry-run
artifacts needed), so it can gate any checkout that has history.
"""

from __future__ import annotations

import argparse
import json
import os

from .common import persist_trajectory, trajectory_path

OBS_TRAJECTORY = "BENCH_obs.json"
# trajectory files the regression gate watches
GATE_FILES = ("BENCH_adaptive.json", "BENCH_obs.json", "BENCH_kernels.json",
              "BENCH_recovery.json", "BENCH_fleet.json")
# Default tolerance: trajectory history spans machines (BENCH files are
# committed), so wall-clock metrics need 2x headroom; tighten with
# --gate-tol when gating same-machine runs.
GATE_TOL = 1.0
GATE_WINDOW = 5         # trailing entries (per section+scale) to median

# op-class histograms worth tracking release-over-release (the rest stay
# inspectable via `python -m repro.obs summarize` on a --trace dump)
OBS_HISTS = ("write_us", "multi_get_us", "stall_us", "flush_us",
             "compact_us", "gc_us", "gc_rewrite_bytes",
             "gc_reclaimed_bytes", "kernel_rank_probe_us",
             "kernel_interval_rank_us", "kernel_lookup_probe_us",
             "kernel_run_coalesce_us", "kernel_segment_reduce_us")
OBS_ENGINES = ("rocksdb", "scavenger", "scavenger_adaptive")


def pairs():
    from .roofline import load_cells
    base = {(c["arch"], c["shape"], c["mesh"]): c
            for c in load_cells(opt=False)}
    opt = {(c["arch"], c["shape"], c["mesh"]): c
           for c in load_cells(opt=True)}
    for key in sorted(set(base) & set(opt)):
        yield key, base[key], opt[key]


def report_rows() -> list[dict]:
    """-> trajectory rows: one per (cell, mesh, term) with the speedup."""
    from .roofline import BASELINE, OPTIMIZED, analyze
    rows = []
    for (arch, shape, mesh), b, o in pairs():
        ab, ao = analyze(b, BASELINE), analyze(o, OPTIMIZED)
        for name, bv, ov in [
            ("memory_s", ab["t_memory_s"], ao["t_memory_s"]),
            ("collective_s", ab["t_collective_s"], ao["t_collective_s"]),
            ("roofline_frac", ab["roofline_frac"], ao["roofline_frac"]),
            ("temp_gb_hlo", ab["temp_bytes"] / 1e9, ao["temp_bytes"] / 1e9),
            ("coll_gb_hlo", ab["hlo_collective_bytes"] / 1e9,
             ao["hlo_collective_bytes"] / 1e9),
        ]:
            # None (not inf) when the optimized term is 0: float('inf')
            # serializes as the non-RFC-8259 token "Infinity" and would
            # corrupt the JSON trajectory for strict parsers
            rows.append({"cell": f"{arch}/{shape}", "mesh": mesh,
                         "term": name, "baseline": bv, "optimized": ov,
                         "x": (bv / ov) if ov else None})
    return rows


def obs_rows(engines=OBS_ENGINES) -> list[dict]:
    """Tail-latency rows — p50/p95/p99 per op-class histogram, merged
    across shards, from an observed load+update run per engine."""
    from repro.obs import Observer
    from repro.workloads import mixed_8k

    from .common import ds_bytes, load_update

    rows = []
    for engine in engines:
        obs = Observer()
        st = load_update(engine, mixed_8k(dataset_bytes=ds_bytes(4)),
                         observer=obs)
        st["runner"].read(512)          # populate multi_get_us
        obs.finish()
        for name in OBS_HISTS:
            h = obs.metrics.merged(name)
            if not h.count:
                continue
            rows.append({"engine": engine, "metric": name,
                         "count": h.count, "mean": h.mean,
                         "p50": h.quantile(0.50), "p95": h.quantile(0.95),
                         "p99": h.quantile(0.99)})
    return rows


# ------------------------------------------------- regression gate (§11)
def _row_metrics(row: dict):
    """-> (key, {metric: value}) for a trajectory row, or None for row
    shapes the gate does not track (e.g. perf_report's analytic cells,
    whose baseline/optimized terms are model outputs, not measurements)."""
    if "name" in row and "us_per_call" in row:
        out = {"us_per_call": row["us_per_call"]}
        # fleet rows (benchmarks/elasticity.py) expose migration fence
        # downtime as a typed key so regressions fail the gate (§14)
        if isinstance(row.get("fence_ms"), (int, float)):
            out["fence_ms"] = row["fence_ms"]
        return row["name"], out
    if "engine" in row and "metric" in row and "p99" in row:
        return f"{row['engine']}/{row['metric']}", {"p99": row["p99"]}
    if "engine" in row and "us_per_update" in row:
        key = f"{row['engine']}/{row.get('workload', '-')}"
        out = {"us_per_update": row["us_per_update"]}
        if "space_amp" in row:
            out["space_amp"] = row["space_amp"]
        return key, out
    return None


def gate(tol: float = GATE_TOL, window: int = GATE_WINDOW,
         files=GATE_FILES, out=None) -> int:
    """Compare each trajectory section's newest entry against the median
    of its trailing window (same section, same scale).  Returns the number
    of regressed metrics; prints one line per failure."""
    import sys
    out = out or sys.stdout
    failures = checked = 0
    for fname in files:
        path = trajectory_path(fname)
        if not os.path.isfile(path):
            continue
        try:
            with open(path) as f:
                data = json.load(f)
        except json.JSONDecodeError:
            print(f"{fname}: unreadable, skipped", file=out)
            continue
        if not isinstance(data, list):
            continue
        groups: dict[tuple, list] = {}
        for e in data:
            if isinstance(e, dict) and "rows" in e:
                groups.setdefault((e.get("section", "?"), e.get("scale")),
                                  []).append(e)
        for (section, scale), entries in sorted(groups.items()):
            if len(entries) < 2:
                continue        # no history yet: nothing to gate against
            latest, trail = entries[-1], entries[-1 - window:-1]
            hist: dict[tuple, list] = {}
            for e in trail:
                for r in e["rows"]:
                    km = _row_metrics(r)
                    if km is None:
                        continue
                    for m, v in km[1].items():
                        if isinstance(v, (int, float)):
                            hist.setdefault((km[0], m), []).append(v)
            for r in latest["rows"]:
                km = _row_metrics(r)
                if km is None:
                    continue
                for m, v in km[1].items():
                    vals = hist.get((km[0], m))
                    if not vals or not isinstance(v, (int, float)):
                        continue
                    ref = sorted(vals)[len(vals) // 2]
                    checked += 1
                    if ref > 0 and v > ref * (1.0 + tol):
                        failures += 1
                        print(f"GATE FAIL {fname}:{section}[{scale}] "
                              f"{km[0]} {m}: {v:.4g} vs trailing median "
                              f"{ref:.4g} (tol {tol:.0%})", file=out)
    print(f"perf gate: {checked} metrics checked, {failures} regressed "
          f"(tol {tol:.0%}, window {window})", file=out)
    return failures


def report():
    rows = report_rows()
    print("| cell | mesh | term | baseline | optimized | x |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        x = f"{r['x']:.1f}" if r["x"] is not None else "inf"
        print(f"| {r['cell']} | {r['mesh']} | {r['term']} | "
              f"{r['baseline']:.4g} | {r['optimized']:.4g} | {x} |")
    path = persist_trajectory("perf_report", rows)
    print(f"# trajectory appended to {path}")
    orows = obs_rows()
    print("| engine | metric | count | mean | p50 | p95 | p99 |")
    print("|---|---|---|---|---|---|---|")
    for r in orows:
        print(f"| {r['engine']} | {r['metric']} | {r['count']} | "
              f"{r['mean']:.4g} | {r['p50']:.4g} | {r['p95']:.4g} | "
              f"{r['p99']:.4g} |")
    opath = persist_trajectory("obs_tails", orows,
                               path=trajectory_path(OBS_TRAJECTORY))
    print(f"# obs trajectory appended to {opath}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmarks.perf_report",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--gate", action="store_true",
                    help="regression-gate the BENCH_*.json trajectories "
                         "(exit 1 on regression); skips the report")
    ap.add_argument("--gate-tol", type=float, default=GATE_TOL,
                    help="allowed fractional slowdown vs trailing median")
    ap.add_argument("--gate-window", type=int, default=GATE_WINDOW,
                    help="trailing entries per section to compare against")
    args = ap.parse_args(argv)
    if args.gate:
        return 1 if gate(tol=args.gate_tol, window=args.gate_window) else 0
    report()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
