"""Run one benchmark cell once on the chip this process is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic and metrics are read from
``BENCHMARK.json`` and the files under ``chipbench/``.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``), then
``compared``: each number the check compared, with its limit.  Exits 2,
with no result line, when JAX finds no TPU or fewer chips than the cell
asks for.
"""

import time

T_PROCESS = time.perf_counter()     # set-up is timed from process start

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench.harness import NoChip, run_cell
    try:
        run_cell(ROOT, args.workload, args.seed, args.seconds,
                 bool(args.trace), T_PROCESS)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
