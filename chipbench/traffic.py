"""The benchmark's one traffic generator, driven by data.

A configuration file (``configs/<name>.json``) fixes the data set: record
count, value sizes, the zipfian constant and the pre-update factor.  A
traffic file (``traffic/<name>.json``) fixes the requests: ops per request
and the share of each request kind.  Everything is drawn from ``--seed``
during set-up, vectorised, so no generator work falls in the window.

The key and value distributions are copies of ``ZipfKeys`` and ``Mixed``
from ``repro.workloads.generator`` (and of ``splitmix64`` from
``repro.core.engine.keys``): the yardstick must not move when the program
does.  Keys are YCSB's ``insertorder=ordered`` integers; the zipfian
scramble is fixed (YCSB hashes ranks with a fixed FNV hash), so every seed
has the same hot set and draws different requests from it.
"""

from __future__ import annotations

import dataclasses
from math import gcd

import numpy as np

LOAD_BATCH = 1 << 16        # records per WriteBatch while building the store

_GAMMA = np.uint64(0x9E3779B97F4A7C15)
_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)
_SCRAMBLE = np.uint64(1)    # fixed zipfian scramble key (seed-independent)


def splitmix64(x) -> np.ndarray:
    with np.errstate(over="ignore"):
        z = np.asarray(x, dtype=np.uint64) + _GAMMA
        z = (z ^ (z >> np.uint64(30))) * _MIX1
        z = (z ^ (z >> np.uint64(27))) * _MIX2
        return z ^ (z >> np.uint64(31))


class ZipfKeys:
    """Scrambled zipfian over [0, n): an inverse-CDF table for the 10,000
    hottest ranks and a uniform tail, ranks scrambled over the key space."""

    def __init__(self, n: int, theta: float):
        self.n = n
        head = min(n, 10_000)
        w = np.arange(1, head + 1, dtype=np.float64) ** (-theta)
        tail = (((n ** (1 - theta)) - (head ** (1 - theta))) / (1 - theta)
                if n > head else 0.0)
        self._head = head
        self._head_cdf = np.cumsum(w) / (w.sum() + tail)

    def sample(self, rng: np.random.Generator, m: int) -> np.ndarray:
        u = rng.random(m)
        is_head = u < self._head_cdf[-1]
        out = np.empty(m, np.int64)
        out[is_head] = np.searchsorted(self._head_cdf, u[is_head])
        n_tail = int((~is_head).sum())
        if n_tail:
            out[~is_head] = rng.integers(self._head, self.n, n_tail)
        scram = splitmix64(out.astype(np.uint64) ^ _SCRAMBLE)
        return (scram % np.uint64(self.n)).astype(np.uint64)


def value_sizes(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """Value sizes per the configuration's ``value`` entry: ``fixed`` or
    ``mixed`` (the paper's Mixed-8K: a ``large_frac`` share of ``large``
    bytes, the rest uniform in [small_lo, small_hi])."""
    if spec["kind"] == "fixed":
        return np.full(n, spec["bytes"], np.int64)
    if spec["kind"] == "mixed":
        is_large = rng.random(n) < spec["large_frac"]
        small = rng.integers(spec["small_lo"], spec["small_hi"] + 1, n)
        return np.where(is_large, spec["large"], small).astype(np.int64)
    raise ValueError(f"unknown value kind {spec['kind']!r}")


@dataclasses.dataclass
class Request:
    """One client request: a batch of one type.  ``kind`` is ``read``
    (``multi_get``), ``write`` (one ``WriteBatch`` of puts) or ``scan``
    (``multi_scan``); ``op`` names the traffic entry it came from."""
    kind: str
    op: str
    keys: np.ndarray | None = None      # read / write keys
    sizes: np.ndarray | None = None     # write value sizes
    lens: np.ndarray | None = None      # scan lengths (keys = starts)

    @property
    def n_ops(self) -> int:
        return len(self.keys)


@dataclasses.dataclass
class SetupOps:
    """The writes that build the store: the ordered load, then the
    zipfian pre-update, each cut into ``LOAD_BATCH`` WriteBatches."""
    load_keys: np.ndarray
    load_sizes: np.ndarray
    update_keys: np.ndarray
    update_sizes: np.ndarray

    def batches(self):
        for keys, sizes in ((self.load_keys, self.load_sizes),
                            (self.update_keys, self.update_sizes)):
            for i in range(0, len(keys), LOAD_BATCH):
                yield keys[i:i + LOAD_BATCH], sizes[i:i + LOAD_BATCH]


def setup_ops(cfg: dict, rng: np.random.Generator) -> SetupOps:
    n = cfg["records"]
    load_keys = np.arange(n, dtype=np.uint64)
    load_sizes = value_sizes(cfg["value"], rng, n)
    m = int(cfg["preupdate_factor"] * n)
    zipf = ZipfKeys(n, cfg["zipf_theta"])
    return SetupOps(load_keys, load_sizes, zipf.sample(rng, m),
                    value_sizes(cfg["value"], rng, m))


def kind_pattern(mix: list[dict], rng: np.random.Generator,
                 n: int) -> np.ndarray:
    """Index into ``mix`` per request.  Shares are exact in every block of
    the smallest request count that holds them in whole numbers, shuffled
    inside the block: every seed gets the same amounts of each kind, in
    another order."""
    shares = [round(m["share"] * 100) for m in mix]
    if sum(shares) != 100:
        raise ValueError(f"request shares must sum to 1: {mix}")
    g = 0
    for s in shares:
        g = gcd(g, s)
    block = np.repeat(np.arange(len(mix)), [s // g for s in shares])
    reps = -(-n // len(block))
    return np.concatenate([rng.permutation(block)
                           for _ in range(reps)])[:n]


class Stream:
    """Pre-drawn request stream of one cell: ``requests[i]`` is the i-th
    request the client sends; the window takes them in order."""

    def __init__(self, cfg: dict, traffic: dict, rng: np.random.Generator,
                 n_requests: int):
        n, ops, mix = cfg["records"], traffic["request_ops"], traffic["mix"]
        zipf = ZipfKeys(n, cfg["zipf_theta"])
        kinds = kind_pattern(mix, rng, n_requests)
        draws = [self._draw(m, cfg, zipf, rng, int((kinds == i).sum()), ops)
                 for i, m in enumerate(mix)]
        taken = [0] * len(mix)
        next_key = n                          # inserts: ordered new keys
        self.requests: list[Request] = []
        for k in kinds.tolist():
            op, (keys, sizes, lens), j = mix[k]["op"], draws[k], taken[k]
            taken[k] += 1
            if op == "read":
                self.requests.append(Request("read", op, keys[j]))
            elif op == "update":
                self.requests.append(Request("write", op, keys[j], sizes[j]))
            elif op == "insert":
                ks = np.arange(next_key, next_key + ops, dtype=np.uint64)
                next_key += ops
                self.requests.append(Request("write", op, ks, sizes[j]))
            else:
                self.requests.append(Request("scan", op, keys[j],
                                             lens=lens[j]))

    @staticmethod
    def _draw(m: dict, cfg: dict, zipf: ZipfKeys, rng, cnt: int, ops: int):
        """(keys, sizes, lens) arrays of shape (cnt, ops) for one entry of
        the traffic's mix; None where the kind has no such column."""
        keys = sizes = lens = None
        if m["op"] in ("read", "update"):
            keys = zipf.sample(rng, cnt * ops).reshape(cnt, ops)
        elif m["op"] == "scan":
            keys = (zipf.sample(rng, cnt * ops) if m["starts"] == "zipfian"
                    else rng.integers(0, cfg["records"], cnt * ops)
                    .astype(np.uint64)).reshape(cnt, ops)
            lo, hi = m["length"]
            lens = rng.integers(lo, hi + 1, (cnt, ops))
        elif m["op"] != "insert":
            raise ValueError(f"unknown request op {m['op']!r}")
        if m["op"] in ("update", "insert"):
            sizes = value_sizes(cfg["value"], rng, cnt * ops).reshape(cnt, ops)
        return keys, sizes, lens
