"""The comparison that decides ``correct``.

Every answer of the run is checked against the plain reference
(``reference.LastWriteWins``), replayed in the order the client sent the
requests: the set-up writes, the warm-up and window requests, the
untimed requests after the window, then the read-back.  Each number
compared is a count of wrong answers, exact, with the limit 0:

  ack_bad        acknowledged vids that are 0 or repeat an earlier one
  read_wrong     keys a ``multi_get`` answered otherwise than the
                 reference (found, vid and value size)
  scan_wrong     ``multi_scan`` results that differ from the reference's
  readback_wrong keys read back after the window that differ

Requests after the window (``n_timed`` on) count in no ``failed``.

The reference takes each write's vids as the store acknowledged them, so
a read that misses an acknowledged write, an answer altered where it is
produced, or a write that is acknowledged and not applied all count.
"""

from __future__ import annotations

import dataclasses

import numpy as np

LIMITS = {"ack_bad": 0, "read_wrong": 0, "scan_wrong": 0,
          "readback_wrong": 0}


@dataclasses.dataclass
class CheckResult:
    compared: dict
    correct: bool
    failed_in_window: int
    ratios: list            # stored over live bytes: before, after each


def _wrong(ref, keys, answer) -> int:
    found, vid, vsize = answer
    f, v, s = ref.get(keys)
    bad = ((np.asarray(found, bool) != f)
           | (np.asarray(vid, np.uint64) != v)
           | (np.where(f, np.asarray(vsize, np.int64), 0) != s))
    return int(bad.sum())


def _scan_wrong(ref, req, answer) -> int:
    if len(answer) != len(req.keys):
        return len(req.keys)
    return sum(
        [(int(k), int(v)) for k, v in got] != ref.scan(int(s), int(c))
        for s, c, got in zip(req.keys.tolist(), req.lens.tolist(), answer))


def check_run(ref, setup_batches, acked, done, n_warm, readback,
              space0, n_timed=None) -> CheckResult:
    """``space0``: stored bytes after set-up (None: no space statistic);
    ``ratios`` then holds stored over live bytes before the first request
    and after each one."""
    counts = dict.fromkeys(LIMITS, 0)
    seen_vids = []
    for (keys, sizes), vids in zip(setup_batches, acked):
        ref.apply(keys, vids, sizes)
        seen_vids.append(np.asarray(vids, np.uint64))
    ratios = [] if space0 is None else [space0 / max(ref.live_bytes, 1)]
    timed_end = len(done) if n_timed is None else n_timed
    failed = 0
    for i, d in enumerate(done):
        r = d.req
        bad = 0
        if r.kind == "write":
            vids = np.asarray(d.answer, np.uint64)
            if len(vids) != len(r.keys):
                bad = len(r.keys)
                vids = np.zeros(len(r.keys), np.uint64)
            ref.apply(r.keys, vids, r.sizes)
            seen_vids.append(vids)
            counts["ack_bad"] += bad
        elif r.kind == "read":
            bad = _wrong(ref, r.keys, d.answer)
            counts["read_wrong"] += bad
        else:
            bad = _scan_wrong(ref, r, d.answer)
            counts["scan_wrong"] += bad
        if n_warm <= i < timed_end:
            failed += bad > 0
        if space0 is not None:
            ratios.append(d.space / max(ref.live_bytes, 1))
    allv = np.concatenate(seen_vids) if seen_vids else np.zeros(0, np.uint64)
    dup = len(allv) - len(np.unique(allv))
    counts["ack_bad"] += int((allv == 0).sum()) + dup
    for keys, answer in readback:
        counts["readback_wrong"] += _wrong(ref, keys, answer)
    compared = {k: {"value": v, "limit": LIMITS[k]}
                for k, v in counts.items()}
    correct = all(v <= LIMITS[k] for k, v in counts.items())
    return CheckResult(compared, correct, failed, ratios)
