"""One run of one cell: set-up, warm-up, a closed-loop window, the check.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

  * ``chipbench/configs/<config>.json``  the data set and the store's sizes
  * ``chipbench/traffic/<traffic>.json`` the requests (``traffic.Stream``)
  * ``chipbench/metrics/<metric>.py``    a per-layer reader, ``read(run)``
  * ``chipbench/end_to_end/<metric>.json`` the statistic of an end-to-end
    metric (``end_to_end``)

A run makes its data from the seed, builds the store through
``Store.write``, warms up on the cell's own requests until none has built
a new executable for ``WARM_QUIET_S`` seconds (the background jobs that
writes set off build theirs too), then one client sends requests back to
back for the window.
Once the window has closed it reads the device's peak memory.  An
untraced run of a cell with a space statistic then sends the stream's
next requests, untimed, until warm-up, window and these have written the
traffic's ``space_volume`` times the configuration's logical bytes.  It
reads back a sample of the store (cells that write), frees the store and
checks every answer of the run against ``reference.LastWriteWins``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

import numpy as np

from . import traffic as tgen
from .check import check_run
from .reference import LastWriteWins
from .work import peaks_for

WARM_MIN, WARM_MAX = 32, 1500   # warm-up requests
WARM_QUIET_S = 3.0          # warm-up ends after this long with no new build
TRACE_WINDOW_S = 10         # a traced run's window is at most this long
READBACK_REQUESTS = 32      # 256-key multi_gets after a window that wrote
SPACE_STAT = "space_amp"
REHEARSAL_RECORDS = 4096
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------ the files
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench_dir: Path


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    bench_dir = root / "chipbench"
    cfg = json.loads((root / conf["file"]).read_text())
    tr = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json")
                    .read_text())
    return Cell(name, w["chips"], cfg, tr,
                _for_cell(bench["end_to_end"], name),
                _for_cell(bench["per_layer"], name), bench_dir)


def e2e_spec(bench_dir: Path, metric: str) -> dict:
    return json.loads((bench_dir / "end_to_end" / f"{metric}.json")
                      .read_text())


def load_reader(bench_dir: Path, metric: str):
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def rehearsal_cfg(cfg: dict, records: int = REHEARSAL_RECORDS) -> dict:
    """The configuration cut to ``records`` with every structure size cut
    by the same factor (floor 32 KiB): for CPU rehearsals in the tests."""
    out = json.loads(json.dumps(cfg))
    scale = records / cfg["records"]
    out["records"] = records
    ec = out["engine_config"]
    for k in ("memtable_bytes", "ksst_bytes", "vsst_bytes",
              "base_level_bytes"):
        ec[k] = max(32 << 10, int(ec[k] * scale))
    return out


# -------------------------------------------------------------- devices
def chip_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"JAX found {len(devs)} {devs[0].platform} device(s); "
                     f"the cell needs {chips} TPU chip(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def cpu_device() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": 1}


def memory_peak(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def use_compile_cache(bench_dir: Path) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else one fixed directory in the checkout (git-ignored)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        bench_dir / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts executables JAX builds (each jit-cache miss, whether the
    persistent cache holds it or not), with the seconds each took."""

    def __init__(self):
        import jax.monitoring as mon
        self.names: list[str] = []
        self.seconds: list[float] = []
        self._mon = mon
        mon.register_event_duration_secs_listener(self._on)

    @property
    def n(self) -> int:
        return len(self.names)

    def _on(self, event, duration, fun_name="?", **kw):
        if event == COMPILE_EVENT:
            self.names.append(fun_name)
            self.seconds.append(duration)

    def close(self):
        self._mon.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------- store
def logical_bytes(cfg: dict) -> float:
    v = cfg["value"]
    mean = (v["bytes"] if v["kind"] == "fixed" else
            v["large_frac"] * v["large"]
            + (1 - v["large_frac"]) * (v["small_lo"] + v["small_hi"]) / 2)
    return cfg["records"] * mean


def make_store(cfg: dict, observer=None):
    from repro.core import EngineConfig, Store
    logical = logical_bytes(cfg)
    ec = EngineConfig(
        **cfg["engine_config"], observer=observer,
        cache_bytes=int(cfg["cache_frac"] * logical),
        space_quota_bytes=int(cfg["space_quota_frac"] * logical))
    return Store(ec)


def user_bytes(req: tgen.Request, key_bytes: int) -> int:
    """User bytes a request writes: key plus value bytes per record."""
    if req.kind != "write":
        return 0
    return int((key_bytes + req.sizes).sum())


def stored_bytes(store) -> int:
    """The benchmark's own sum of the version's kSST and vSST sizes."""
    v = store.version
    return (sum(t.file_bytes for lvl in v.levels for t in lvl)
            + sum(t.file_bytes for t in v.value_files.values()))


@dataclasses.dataclass
class Done:
    """One request as sent: what it was, when, and what came back."""
    req: tgen.Request
    t0: float
    t1: float
    answer: object
    space: int = 0          # stored bytes after it (space cells)


def background_state(store) -> tuple:
    """Bytes the store has written to its simulated device, and its count
    of immutable memtables waiting for a flush (0, 0 for a store-shaped
    stand-in without them)."""
    io = getattr(store, "io", None)
    return (io.total_write_bytes() if io is not None else 0,
            len(getattr(store, "immutables", ())))


def send(store, req: tgen.Request, batch):
    if req.kind == "read":
        r = store.multi_get(req.keys)
        return (r["found"], r["vid"], r["vsize"])
    if req.kind == "write":
        return store.write(batch)
    return store.multi_scan(req.keys, req.lens)


# ------------------------------------------------------------------ run
@dataclasses.dataclass
class RunData:
    """What a per-layer reader reads: the traced window's requests, the
    observer's host spans and routed calls, the program's device-write
    counter, and the reduced profiler trace (``trace.Reduced`` or None)."""
    window: list
    t0: float
    t1: float
    spans: list
    kernel_calls: list
    device_write_bytes: int
    user_write_bytes: int
    reduced: object
    peaks: dict | None

    @property
    def read_requests(self) -> list:
        return [d for d in self.window if d.req.kind in ("read", "scan")]

    @property
    def read_keys(self) -> int:
        return sum(d.req.n_ops for d in self.window if d.req.kind == "read")


def _pct(vals, q) -> float:
    return float(np.percentile(np.asarray(vals, np.float64), q))


def end_to_end(cell: Cell, window: list, t0: float, t1: float,
               space: float | None, setup_s: float) -> dict:
    """Each end-to-end metric by the statistic its file
    ``chipbench/end_to_end/<name>.json`` names: ``rate`` (ops over the
    window's seconds), ``p95_ms`` (of the requests of the listed kinds),
    ``space_amp`` (``byte_weighted``) or ``setup``."""
    out = {}
    for m in cell.end_to_end:
        spec = e2e_spec(cell.bench_dir, m["name"])
        stat = spec["stat"]
        if stat == "setup":
            v = setup_s
        elif stat == "rate":
            v = sum(d.req.n_ops for d in window) / (t1 - t0)
        elif stat == "p95_ms":
            v = _pct([(d.t1 - d.t0) * 1e3 for d in window
                      if d.req.kind in spec["kinds"]], 95)
        elif stat == SPACE_STAT:
            v = space
        else:
            raise ValueError(f"{m['name']}: no statistic named {stat!r}")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def byte_weighted(ratios: list, done: list, key_bytes: int,
                  volume: float) -> float:
    """Mean of stored over live bytes across the first ``volume`` user
    bytes written after set-up: each write request's bytes at the ratio in
    force before it (``ratios[k]`` before ``done[k]``), the last request
    cut at ``volume``."""
    total, left = 0.0, volume
    for r, d in zip(ratios, done):
        w = min(user_bytes(d.req, key_bytes), left)
        total += r * w
        left -= w
        if left <= 0:
            break
    return total / volume


def readback_keys(sent: list, records: int, rng) -> list:
    """Keys to read back after requests that wrote: the newest written
    keys, a sample of all keys they wrote, and a sample of the whole key
    space (records they never touched, moved by GC and compaction)."""
    wrote = [d.req.keys for d in sent if d.req.kind == "write"]
    if not wrote:
        return []
    allw = np.concatenate(wrote)
    rev = np.unique(allw[::-1], return_index=True)[1]
    recent = allw[::-1][np.sort(rev)][:READBACK_REQUESTS // 4 * 256]
    distinct = np.unique(allw)
    sample = rng.choice(distinct, min(len(distinct),
                                      READBACK_REQUESTS // 2 * 256),
                        replace=False)
    space = rng.integers(0, records, READBACK_REQUESTS // 4 * 256).astype(
        np.uint64)
    keys = np.concatenate([recent, sample.astype(np.uint64), space])
    return [keys[i:i + 256] for i in range(0, len(keys), 256)]


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             t_process: float, rehearsal: bool = False, store_factory=None,
             out=sys.stdout, err=sys.stderr) -> dict:
    """Run one cell once; print the result line; return it.  With
    ``rehearsal`` (tests only) it runs on any backend at a tiny size;
    ``store_factory`` puts another store in the program's place (the
    control, and the tests' planted faults)."""
    cell = load_cell(root, name)
    cfg = rehearsal_cfg(cell.cfg) if rehearsal else cell.cfg
    device = cpu_device() if rehearsal else chip_device(cell.chips)
    log = lambda msg: print(f"[chipbench] {msg}", file=err, flush=True)  # noqa: E731
    cache = "off" if rehearsal else use_compile_cache(cell.bench_dir)
    log(f"cell {name} seed {seed} device {json.dumps(device)} compile "
        f"cache {cache}")
    compiles = CompileCounter()
    try:
        return _run(cell, cfg, device, seed, seconds, trace, t_process,
                    rehearsal, store_factory, compiles, log, out, err)
    finally:
        compiles.close()


def _run(cell, cfg, device, seed, seconds, trace, t_process, rehearsal,
         store_factory, compiles, log, out, err) -> dict:
    from repro.core import WriteBatch
    from .observe import WallObserver
    rng = np.random.default_rng(seed)
    window_s = min(seconds, TRACE_WINDOW_S) if trace else seconds
    n_req = WARM_MAX + math.ceil(window_s * cell.traffic["max_requests_per_s"])
    t_gen = time.perf_counter()
    setup = tgen.setup_ops(cfg, rng)
    stream = tgen.Stream(cfg, cell.traffic, rng, n_req).requests
    batches = [WriteBatch().puts(r.keys, r.sizes) if r.kind == "write"
               else None for r in stream]
    t_load = time.perf_counter()
    obs = WallObserver() if trace else None
    store = (store_factory or make_store)(cfg, obs)
    acked = [store.write(WriteBatch().puts(k, s)) for k, s in setup.batches()]
    space_cell = any(e2e_spec(cell.bench_dir, m["name"])["stat"] == SPACE_STAT
                     for m in cell.end_to_end)
    space0 = stored_bytes(store) if space_cell else 0
    t_warm = time.perf_counter()
    done: list[Done] = []
    i, t_built = 0, t_warm
    while i < WARM_MAX and (i < WARM_MIN
                            or time.perf_counter() - t_built < WARM_QUIET_S):
        c0 = compiles.n
        t0 = time.perf_counter()
        ans = send(store, stream[i], batches[i])
        done.append(Done(stream[i], t0, time.perf_counter(), ans))
        if space_cell:
            done[-1].space = stored_bytes(store)
        if compiles.n != c0:
            t_built = done[-1].t1
        i += 1
    n_warm = i

    def at(k: int) -> int:
        # past the end of the stream the window's requests come round
        # again, in the same order
        return k if k < len(stream) else n_warm + (k - n_warm) % (
            len(stream) - n_warm)

    profile_dir = None
    if trace:
        from . import trace as tr
        profile_dir = tr.start(cell.bench_dir)
        obs.active = True
    dev_w0, imm0 = background_state(store)
    c_window = compiles.n
    t_window = time.perf_counter()
    setup_s = t_window - t_process
    end = t_window + window_s
    if trace:
        from jax.profiler import TraceAnnotation
        ann = TraceAnnotation("chipbench:window")
        ann.__enter__()
    while True:
        t0 = time.perf_counter()
        if t0 >= end:
            break
        j = at(i)
        if trace:
            with TraceAnnotation(f"client:{stream[j].op}"):
                ans = send(store, stream[j], batches[j])
        else:
            ans = send(store, stream[j], batches[j])
        d = Done(stream[j], t0, time.perf_counter(), ans)
        if space_cell:
            d.space = stored_bytes(store)
        done.append(d)
        i += 1
    t_end = done[-1].t1
    if trace:
        ann.__exit__(None, None, None)
        obs.active = False
    window = done[n_warm:]
    in_window = compiles.n - c_window
    dev_w1, imm1 = background_state(store)
    reduced = None
    if trace:
        reduced = tr.stop_and_reduce(profile_dir, cell.bench_dir)
    peak = 0 if rehearsal else memory_peak(cell.chips)
    log(f"set-up {setup_s:.3f} s: process start to data "
        f"{t_gen - t_process:.3f} s, data {t_load - t_gen:.3f} s, load and "
        f"pre-update {t_warm - t_load:.3f} s, warm-up "
        f"{t_window - t_warm:.3f} s ({n_warm} requests; {c_window} "
        f"executables built so far)")
    if len(done) > len(stream):
        log(f"the window sent {len(window)} requests, more than the "
            f"{len(stream) - n_warm} drawn: raise max_requests_per_s in "
            f"traffic/{cell.traffic['name']}.json")
    log(f"window {t_end - t_window:.3f} s: {len(window)} requests, "
        f"{in_window} executables built inside the window in "
        f"{sum(compiles.seconds[c_window:]):.3f} s "
        f"{compiles.names[c_window:]}")
    key_bytes = cfg["engine_config"]["key_bytes"]
    user_w = sum(user_bytes(d.req, key_bytes) for d in window)
    log(f"window background: {dev_w1 - dev_w0} device bytes written for "
        f"{user_w} user bytes; immutable memtables {imm0} -> {imm1}")
    n_timed = len(done)
    if space_cell and not trace:
        # untimed: the same request sequence a faster run saw, until the
        # space statistic's volume of user bytes is written
        volume = cell.traffic["space_volume"] * logical_bytes(cfg)
        written = timed_w = sum(user_bytes(d.req, key_bytes) for d in done)
        t_after = time.perf_counter()
        while written < volume:
            j = at(i)
            t0 = time.perf_counter()
            ans = send(store, stream[j], batches[j])
            done.append(Done(stream[j], t0, time.perf_counter(), ans,
                             stored_bytes(store)))
            written += user_bytes(stream[j], key_bytes)
            i += 1
        log(f"space volume {volume:.0f} user bytes: warm-up and window "
            f"wrote {timed_w}; after the window {len(done) - n_timed} "
            f"untimed requests in {time.perf_counter() - t_after:.3f} s")
    rb_rng = np.random.default_rng([seed, 1])
    readback = []
    for keys in readback_keys(done[n_warm:], cfg["records"], rb_rng):
        r = store.multi_get(keys)
        readback.append((keys, (r["found"], r["vid"], r["vsize"])))
    run = None
    if trace:
        run = RunData(window, t_window, t_end, obs.spans, obs.kernel_calls,
                      dev_w1 - dev_w0, user_w, reduced,
                      None if rehearsal else peaks_for(device["kind"]))
    del store, obs
    gc.collect()
    t_check = time.perf_counter()
    ref = LastWriteWins(key_bytes)
    res = check_run(ref, list(setup.batches()), acked, done, n_warm,
                    readback, space0 if space_cell else None, n_timed)
    log(f"check {time.perf_counter() - t_check:.3f} s")
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(cell.bench_dir, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        space = None
        if space_cell:
            space = byte_weighted(res.ratios, done, key_bytes, volume)
        metrics = end_to_end(cell, window, t_window, t_end, space, setup_s)
    device = dict(device, memory_peak_bytes=peak)
    if trace:
        device["busy_s"] = reduced.busy_s if reduced else 0.0
        device["window_s"] = reduced.window_s if reduced else 0.0
    result = {"correct": res.correct, "attempted": len(window),
              "failed": res.failed_in_window, "metrics": metrics,
              "device": device}
    if trace and reduced is not None:
        result["breakdown"] = reduced.breakdown()
    result["compared"] = res.compared   # last key of the line
    for k, v in res.compared.items():
        log(f"compared {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), file=out, flush=True)
    return result

