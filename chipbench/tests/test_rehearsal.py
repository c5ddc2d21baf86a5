"""CPU rehearsals: every cell of BENCHMARK.json runs end to end from its
files at a tiny size, and a new traffic file plus a BENCHMARK.json entry
runs with no edit to the harness.  The rehearsal mode exists only here:
``run.py`` cannot select it."""

import io
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from chipbench.harness import run_cell

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2**31 + 12345


def rehearse(root, cell, trace=False, seconds=0.5):
    out, err = io.StringIO(), io.StringIO()
    res = run_cell(root, cell, SEED, seconds, trace, time.perf_counter(),
                   rehearsal=True, out=out, err=err)
    line = out.getvalue().strip().splitlines()[-1]
    assert json.loads(line) == res
    return res, line, err.getvalue()


def _names(section, cell):
    return {m["name"] for m in BENCH[section]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(cell):
    res, line, err = rehearse(ROOT, cell)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == _names("end_to_end", cell)
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(json.loads(line))[-1] == "compared"
    # the numbers compared are also the last lines on standard error
    tail = err.strip().splitlines()[-len(res["compared"]):]
    assert all(line.startswith("[chipbench] compared ") for line in tail)
    assert "0 executables built inside the window" in err


@pytest.mark.parametrize("cell,names", [
    ("ycsb-a.1kb", ("routed_calls_per_read", "route_wall_ms_per_read",
                    "read_host_ms_per_read")),
    ("update.mixed8k", ("write_amp", "bg_wall_pct")),
])
def test_traced_cell_reports_its_layers(cell, names):
    res, _, _ = rehearse(ROOT, cell, trace=True)
    assert res["correct"]
    assert set(res["metrics"]) <= _names("per_layer", cell)
    for name in names:
        assert res["metrics"][name]["value"] > 0, name
    assert {"busy_s", "window_s"} <= set(res["device"])


def test_new_traffic_file_needs_no_harness_edit(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__", "tests"))
    (tmp_path / "chipbench" / "traffic" / "ycsb-b.json").write_text(
        json.dumps({"name": "ycsb-b", "request_ops": 256,
                    "mix": [{"op": "read", "share": 0.95},
                            {"op": "update", "share": 0.05}],
                    "max_requests_per_s": 400}))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "ycsb-b.1kb", "config": "ycsb-1kb",
                               "traffic": "ycsb-b", "chips": 1,
                               "why": "rehearsal of a new mix"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ops_per_s", "read_p95_ms", "write_p95_ms"):
            m["workloads"].append("ycsb-b.1kb")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    res, _, _ = rehearse(tmp_path, "ycsb-b.1kb")
    assert res["correct"]
    assert set(res["metrics"]) == {"ops_per_s", "read_p95_ms",
                                   "write_p95_ms", "setup_s"}


def test_without_a_tpu_the_command_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
