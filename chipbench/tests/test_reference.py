"""The plain reference catches a wrong vid, a missing key and a wrong
scan result, through the same check that decides ``correct``."""

import numpy as np

from chipbench.check import check_run
from chipbench.harness import Done
from chipbench.reference import LastWriteWins
from chipbench.traffic import Request

KEYS = np.arange(10, dtype=np.uint64)
SIZES = np.full(10, 100, np.int64)
VIDS = np.arange(1, 11, dtype=np.uint64)


def _check(answer_of):
    """One set-up batch of 10 puts, then one read of every key and one
    scan of 5 from key 3, answered by ``answer_of``."""
    ref = LastWriteWins(24)
    read = Request("read", "read", KEYS)
    scan = Request("scan", "scan", np.array([3], np.uint64),
                   lens=np.array([5]))
    done = [Done(read, 0.0, 1.0, answer_of("read")),
            Done(scan, 1.0, 2.0, answer_of("scan"))]
    return check_run(ref, [(KEYS, SIZES)], [VIDS], done, 0, [], None)


def _truth(kind):
    if kind == "read":
        return (np.ones(10, bool), VIDS.copy(), SIZES.copy())
    return [[(k, k + 1) for k in range(3, 8)]]


def test_true_answers_pass():
    res = _check(_truth)
    assert res.correct and res.failed_in_window == 0


def test_one_wrong_vid_fails():
    def ans(kind):
        a = _truth(kind)
        if kind == "read":
            a[1][4] += np.uint64(1)
        return a
    res = _check(ans)
    assert not res.correct
    assert res.compared["read_wrong"]["value"] == 1


def test_one_missing_key_fails():
    def ans(kind):
        a = _truth(kind)
        if kind == "read":
            a[0][7] = False
            a[1][7] = 0
            a[2][7] = 0
        return a
    res = _check(ans)
    assert not res.correct
    assert res.compared["read_wrong"]["value"] == 1


def test_one_wrong_scan_fails():
    def ans(kind):
        a = _truth(kind)
        if kind == "scan":
            a[0][2] = (5, 99)
        return a
    res = _check(ans)
    assert not res.correct
    assert res.compared["scan_wrong"]["value"] == 1


def test_last_write_in_a_batch_wins_and_inserts_merge():
    ref = LastWriteWins(24)
    ref.apply([5, 3, 5], [1, 2, 3], [10, 20, 30])
    ref.apply([4, 3], [7, 8], [1, 2])
    found, vid, size = ref.get(np.array([3, 4, 5, 6], np.uint64))
    assert found.tolist() == [True, True, True, False]
    assert vid.tolist() == [8, 7, 3, 0]
    assert size.tolist() == [2, 1, 30, 0]
    assert ref.live_bytes == 3 * 24 + 2 + 1 + 30
    assert ref.scan(4, 5) == [(4, 7), (5, 3)]


def test_repeated_ack_counts():
    ref = LastWriteWins(24)
    res = check_run(ref, [(KEYS, SIZES)], [np.ones(10, np.uint64)], [], 0,
                    [], None)
    assert not res.correct and res.compared["ack_bad"]["value"] == 9
