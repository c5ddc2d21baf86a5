"""Least bytes one call of the probe family must move.

The probe family answers, for a column of Q query keys against one sorted
run (and, for ``lookup``, its bloom filter): membership and rank
(``rank``), plus the k-bit filter test (``lookup``), or the count of
level-file minimums at or below each query (``count_le``).  The count is
of bytes, not operations: a probe does a few integer compares per key
touched, and the chip's integer rate is far above what its memory feeds,
so the least time is bound by bytes.

The least bytes are the ones every implementation of the same call moves,
whether it streams the whole run and filter (the brute-force kernels) or
searches them (``searchsorted``): it reads each query (4 B) and, for a
filter test, its k bit indices (4 B each), and writes each answer: a
found flag (1 B) and a rank (4 B), a may-contain flag (1 B), or a count
(4 B).  Table entries are left out: queries that repeat a key share their
search path, so no count of them holds for every input.  A call carries
at least one 256-row query tile, the padding of the program's dispatch,
so ``q`` is at least 256.  A roofline share over 100% means this count is
too high, or the measured time misses part of the call.
"""

from __future__ import annotations

MIN_QUERY_ROWS = 256


def least_bytes(kind: str, q: int = MIN_QUERY_ROWS, k: int = 7) -> int:
    q = max(q, MIN_QUERY_ROWS)
    if kind == "lookup":
        return q * (4 + 4 * k + 1 + 1 + 4)
    if kind == "rank":
        return q * (4 + 1 + 4)
    if kind == "count_le":
        return q * (4 + 4)
    raise ValueError(f"unknown probe kind {kind!r}")
