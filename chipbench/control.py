"""The control of the check: the plain reference put in the program's
place, with one guarantee the configurations state broken.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3 \
        [--seconds 5]

``StaleReads`` answers every read as of before the newest acknowledged
WriteBatch: "every later read sees it" no longer holds.  The store runs no
model and states no precision, so breaking a guarantee is the step below
it.  The check has to report each run not correct; the benchmark's own
runs never start this.  It drives the cell's full set-up and a short
window on the chip, at the cell's size, and prints one line per seed with
the numbers compared.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.reference import LastWriteWins  # noqa: E402


class StaleReads:
    """Store-shaped reference whose reads lag one acknowledged batch."""

    def __init__(self, key_bytes: int):
        self.cfg = SimpleNamespace(use_kernels=False)
        self.version = SimpleNamespace(levels=[], value_files={})
        self.seen = LastWriteWins(key_bytes)
        self.pending = None
        self.next_vid = 1

    def write(self, batch):
        import numpy as np
        _, keys, vsizes = batch.arrays()
        vids = np.arange(self.next_vid, self.next_vid + len(keys),
                         dtype=np.uint64)
        self.next_vid += len(keys)
        if self.pending is not None:
            self.seen.apply(*self.pending)
        self.pending = (keys, vids, vsizes)
        return vids

    def multi_get(self, keys):
        found, vid, vsize = self.seen.get(keys)
        return {"found": found, "vid": vid, "vsize": vsize}

    def multi_scan(self, starts, counts):
        return [self.seen.scan(int(s), int(c))
                for s, c in zip(starts.tolist(), counts.tolist())]


def stale_factory(cfg: dict, observer=None) -> StaleReads:
    return StaleReads(cfg["engine_config"]["key_bytes"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    from chipbench.harness import run_cell
    for seed in args.seeds:
        line = io.StringIO()
        res = run_cell(ROOT, args.workload, seed, args.seconds, False,
                       time.perf_counter(), store_factory=stale_factory,
                       out=line)
        print(json.dumps({"control": "stale_reads", "workload":
                          args.workload, "seed": seed,
                          "correct": res["correct"],
                          "compared": res["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
