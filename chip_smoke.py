"""Chip smoke run: the store's kernel-routed read path and the serving and
training tiers, each once, on one TPU chip.

    python chip_smoke.py [--records N] [--seed S] [--out DIR]

One process holds the chip for every phase:

  store  ``Store(EngineConfig())`` at the paper's default structure sizes
         (64 MB memtable/kSST, 256 MB vSST, 10 bits/key) under the 1.5x
         space quota: load N 1 KB records with dense keys, one zipfian-0.99
         update pass (flush, compaction and GC each run), then 16
         ``multi_get`` batches of 1024 keys and a few 100-entry
         ``multi_scan``s; then a shorter ``scavenger_adaptive`` pass so the
         tracker's segment-reduce kernels run.  Every answer is checked
         against an oracle of last-written vids, and ``stats()`` plus every
         returned column against the same op stream replayed with
         ``use_kernels=False``.  The replay is host-only NumPy and runs in
         a child process that never imports JAX, beside the chip run.
  serve  smollm-360m at its published width through ``ServeEngine``
         (one slot), 4 requests of 16 new tokens, each token checked
         against a greedy ``model.forward`` over the same sequence.
  train  ``repro.launch.train.run`` on smollm-360m at full width for 3
         steps with one checkpoint save, read back with ``load_pytree``.

Exits non-zero, before any phase and without a result line, when JAX finds
no TPU.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

VALUE_BYTES = 1024
SPACE_QUOTA = 1.5           # the paper's space limit, x logical data
UPDATE_FRAC = 0.5           # zipfian updates per loaded record
WRITE_BATCH = 1 << 16
GET_BATCHES, GET_KEYS = 16, 1024
SCANS, SCAN_LEN = 4, 100
ADAPTIVE_RECORDS = 1 << 20  # the shorter scavenger_adaptive pass
ADAPTIVE_UPDATES = 2        # its zipfian updates per loaded record
SERVE_REQUESTS, SERVE_NEW = 4, 16
SERVE_REF_LEN = 64          # padded length of the greedy reference pass
LOGIT_TOL = 0.05            # top-2 logit gap under which bf16 may flip
KERNEL_OPS = ("lookup_probe", "run_coalesce", "segment_reduce")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(ok, msg: str) -> None:
    """A failed check ends the run (asserts would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


# ------------------------------------------------------------------ store
def _put(store, keys, oracle):
    """Write 1 KB puts in WriteBatch chunks; record the last vid per key."""
    import numpy as np
    from repro.core import WriteBatch
    for i in range(0, len(keys), WRITE_BATCH):
        kc = keys[i:i + WRITE_BATCH]
        vids = store.write(WriteBatch().puts(
            kc, np.full(len(kc), VALUE_BYTES, np.int64)))
        last, at = np.unique(kc[::-1], return_index=True)
        oracle[last] = vids[::-1][at]


def drive_store(engine: str, records: int, updates: int, seed: int,
                use_kernels: bool = True, observer=None) -> dict:
    """Load, update, read and scan one store; return what it answered.
    The op stream depends only on (engine, records, updates, seed)."""
    import numpy as np
    from repro.core import EngineConfig, Store, accel
    from repro.workloads.generator import ZipfKeys
    cfg = EngineConfig(
        engine=engine, use_kernels=use_kernels, observer=observer,
        space_quota_bytes=int(SPACE_QUOTA * records * VALUE_BYTES))
    store = Store(cfg)
    rng = np.random.default_rng(seed)
    oracle = np.zeros(records, np.uint64)
    t0 = time.perf_counter()
    _put(store, rng.permutation(records).astype(np.uint64), oracle)
    t1 = time.perf_counter()
    upd = ZipfKeys(records, 0.99, seed).sample(rng, updates).astype(
        np.uint64)
    _put(store, upd, oracle)
    t2 = time.perf_counter()
    get_keys = [rng.integers(0, records, GET_KEYS).astype(np.uint64)
                for _ in range(GET_BATCHES)]
    gets = [store.multi_get(k) for k in get_keys]
    t3 = time.perf_counter()
    starts = rng.integers(0, records, SCANS)
    scans = store.multi_scan(starts, SCAN_LEN)
    t4 = time.perf_counter()
    kssts = list(store.version.all_kssts())
    return {
        "stats": store.stats(), "gets": gets, "get_keys": get_keys,
        "starts": starts, "scans": scans, "oracle": oracle,
        "levels": [len(lv) for lv in store.version.levels],
        "largest_run": max((t.n for t in kssts), default=0),
        "wall_s": {"load": t1 - t0, "update": t2 - t1, "multi_get": t3 - t2,
                   "multi_scan": t4 - t3},
        "mode": accel.policy_of(cfg).mode if use_kernels else "host",
    }


def _replay(specs, conn) -> None:
    """Child process: the same op streams with kernels off (host NumPy
    only — this process never imports JAX)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    out = []
    for spec in specs:
        r = drive_store(*spec, use_kernels=False)
        out.append({k: r[k] for k in ("stats", "gets", "scans")})
    conn.send(out)
    conn.close()


def check_store(run: dict, replay: dict, records: int) -> None:
    """Oracle and kernels-off parity checks for one store run."""
    import numpy as np
    oracle = run["oracle"]
    for keys, res, ref in zip(run["get_keys"], run["gets"], replay["gets"]):
        require(res["found"].all(), "loaded key not found")
        require((res["vid"] == oracle[keys]).all(), "stale or wrong vid")
        for col in ("found", "vid", "vsize", "etype"):
            require(np.array_equal(res[col], ref[col]), f"{col} != replay")
    for s, got in zip(run["starts"].tolist(), run["scans"]):
        want = [(k, int(oracle[k]))
                for k in range(s, min(s + SCAN_LEN, records))]
        require([(int(k), int(v)) for k, v in got] == want,
                "scan mismatch")
    require(run["scans"] == replay["scans"], "scans != replay")
    require(run["stats"] == replay["stats"],
            f"stats != replay:\n{run['stats']}\n{replay['stats']}")


def store_phase(records: int, seed: int) -> dict:
    import multiprocessing as mp
    from repro.kernels.common import column_len
    from repro.obs import Observer
    small = min(records, ADAPTIVE_RECORDS)
    specs = [("scavenger", records, int(UPDATE_FRAC * records), seed),
             ("scavenger_adaptive", small, ADAPTIVE_UPDATES * small, seed)]
    log(f"store: {records} records x {VALUE_BYTES} B = "
        f"{records * VALUE_BYTES / 2**30:.2f} GiB logical (the paper loads "
        f"100 GB; cut for run time), {UPDATE_FRAC} zipfian-0.99 updates per "
        f"record (the paper: 3), quota {SPACE_QUOTA}x, EngineConfig() "
        f"structure sizes")
    ctx = mp.get_context("spawn")
    recv, send = ctx.Pipe(duplex=False)
    child = ctx.Process(target=_replay, args=(specs, send), daemon=True)
    child.start()
    send.close()
    obs = Observer()
    try:
        runs = [drive_store(*spec, observer=obs) for spec in specs]
        replays = recv.recv()
    finally:
        child.join(timeout=60)
        if child.is_alive():
            child.kill()
    require(child.exitcode == 0, f"replay child exited {child.exitcode}")
    for (engine, n, _, _), run, rep in zip(specs, runs, replays):
        st, big = run["stats"], run["largest_run"]
        log(f"store[{engine}]: mode {run['mode']}, levels {run['levels']}, "
            f"compactions {st['n_compactions']}, gc runs {st['n_gc_runs']}, "
            f"space amp {st['space_amp']}, largest kSST run {big} entries "
            f"(padded {column_len(big)})")
        log(f"store[{engine}]: host wall s {json.dumps(run['wall_s'])}")
        require(sum(run["levels"]) > 0, "no flush ran")
        require(st["n_compactions"] > 0, "no compaction ran")
        require(st["n_gc_runs"] > 0, "no GC ran")
        check_store(run, rep, n)
        log(f"store[{engine}]: {GET_BATCHES}x{GET_KEYS} gets and {SCANS} "
            f"scans match the oracle and the use_kernels=False replay")
    counts = {}
    for op in KERNEL_OPS:
        h = obs.metrics.merged(f"kernel_{op}_us")
        counts[op] = h.count
        log(f"kernel_{op}: {h.count} routed calls, {h.total} us host wall")
    require(all(counts.values()), f"an op never reached the chip: {counts}")
    return {"mode": runs[0]["mode"], "counts": counts}


# ------------------------------------------------------------------ serve
def serve_phase(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.model import build_model
    from repro.serve.engine import Request, ServeEngine
    model = build_model(cfg)
    params = model.init_params(jax.random.key(seed))
    engine = ServeEngine(model, params, batch_slots=1, cache_len=128)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(
                4, cfg.vocab, int(rng.integers(4, 16))).tolist(),
                max_new=SERVE_NEW) for i in range(SERVE_REQUESTS)]
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    engine.run()
    log(f"serve[{cfg.name}]: {SERVE_REQUESTS} requests x {SERVE_NEW} "
        f"tokens in {time.perf_counter() - t0} s host wall (compile "
        f"included)")
    forward = jax.jit(model.forward)
    flips, worst = 0, 0.0
    for r in reqs:
        require(r.done and len(r.out) == SERVE_NEW, "request not finished")
        seq = r.prompt + r.out
        toks = np.zeros((1, SERVE_REF_LEN), np.int32)
        toks[0, :len(seq)] = seq
        logits = np.asarray(forward(params, {"tokens": jnp.asarray(toks)})
                            [0, :, :cfg.vocab], np.float32)
        for i, tok in enumerate(r.out):
            row = logits[len(r.prompt) - 1 + i]
            top2 = np.argsort(row)[-2:][::-1]
            if tok == top2[0]:
                continue
            gap = float(row[top2[0]] - row[top2[1]])
            require(tok == top2[1] and gap <= LOGIT_TOL,
                    f"rid {r.rid} token {i}: engine {tok}, forward top-2 "
                    f"{top2.tolist()} gap {gap}")
            flips, worst = flips + 1, max(worst, gap)
    log(f"serve[{cfg.name}]: tokens match the greedy forward reference "
        f"({flips} top-2 flips within tolerance {LOGIT_TOL}, widest gap "
        f"{worst})")


# ------------------------------------------------------------------ train
def train_phase(arch: str, smoke: bool, out: Path, seed: int) -> None:
    import jax
    import numpy as np
    from repro.checkpoint.pytree import load_pytree
    from repro.checkpoint.store import CheckpointStore
    from repro.configs import get_config
    from repro.launch.train import run
    from repro.models.model import build_model
    ckpt = out / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    steps = 3
    args = argparse.Namespace(
        arch=arch, smoke=smoke, steps=steps, batch=2, seq=512, lr=1e-3,
        accum=1, seed=seed, log_every=1, ckpt_dir=str(ckpt),
        ckpt_engine="scavenger", ckpt_every=steps, keep_last=2,
        quota_mb=None, log_target_kb=1024, fail_at_step=None, fresh=True)
    t0 = time.perf_counter()
    res = run(args)
    log(f"train[{arch}]: {res['steps_run']} steps, losses {res['losses']}, "
        f"{time.perf_counter() - t0} s host wall (compile and save "
        f"included)")
    require(res["steps_run"] == steps, f"ran {res['steps_run']} steps")
    require(all(np.isfinite(res["losses"])), "non-finite loss")
    store = CheckpointStore(str(ckpt), engine="scavenger")
    try:
        like = build_model(get_config(arch, smoke=smoke)).abstract_params()
        back = load_pytree(store, "train", steps, like)
    finally:
        store.close()
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(like)):
        require(got.shape == want.shape and got.dtype == want.dtype,
                f"checkpoint leaf {got.shape} {got.dtype} != {want}")
        require(np.isfinite(np.asarray(got, np.float32)).all(),
                "non-finite checkpoint leaf")
    log(f"train[{arch}]: checkpoint step {steps} reads back "
        f"({len(jax.tree.leaves(back))} leaves)")
    shutil.rmtree(ckpt, ignore_errors=True)


# ------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--records", type=int, default=1 << 23)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(REPO / ".chip_smoke"))
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device {json.dumps(device)}")
    from repro.configs import get_config
    from repro.launch.cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    t0 = time.perf_counter()
    store = store_phase(args.records, args.seed)
    require(store["mode"] == "pallas", f"kernel mode {store['mode']}")
    log(f"phase store ok in {time.perf_counter() - t0} s")
    t0 = time.perf_counter()
    serve_phase(get_config("smollm-360m"), args.seed)
    log(f"phase serve ok in {time.perf_counter() - t0} s")
    t0 = time.perf_counter()
    train_phase("smollm-360m", False, out, args.seed)
    log(f"phase train ok in {time.perf_counter() - t0} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
