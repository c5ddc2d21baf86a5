"""The reduction from a profiler trace to busy time, idle share, kernel
time by the name table, and idle gaps named by host spans: on a small
trace recorded on a TPU v5e (a quarter second of ``ycsb-c.1kb``) and on
events made by hand."""

import json
from pathlib import Path

import pytest

from chipbench.trace import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE,
                             WINDOW_SPAN, op_name, reduce)

HERE = Path(__file__).parent
NAMES = json.loads((HERE.parent / "kernel_names.json").read_text())
DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur):
    return [plane, line, name, float(start), float(dur)]


def test_hand_made_trace():
    events = [
        ev(HOST, "python3", WINDOW_SPAN, 0, 1000),
        ev(HOST, "python3", "client:read", 50, 600),
        ev(HOST, "python3", "store:multi_get", 60, 550),
        ev(HOST, "python3", "client:update", 700, 250),
        ev(DEV, OPS_LINE, "rank_probe_pallas", 100, 100),
        ev(DEV, OPS_LINE, "copy", 150, 100),          # overlaps: union
        ev(DEV, OPS_LINE, "copy", 900, 200),          # runs past the end
        ev(DEV, MODULES_LINE, "jit_rank_probe_pallas(1)", 100, 120),
        ev(DEV, MODULES_LINE, "jit_squeeze(2)", 230, 10),
        ev(DEV, OPS_LINE, "fusion", -50, 20),         # before the window
    ]
    red = reduce(events, NAMES)
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(250e-9)         # [100,250) + [900,1000)
    assert red.idle_share == pytest.approx(0.75)
    assert red.kernels == {"probe.rank": {"seconds": pytest.approx(120e-9),
                                          "calls": 1}}
    gaps = dict((round(s * 1e9), name) for name, s in red.idle_gaps)
    # [250, 900) middle 575 -> multi_get; [0, 100) middle 50 -> client:read
    assert gaps == {650: "store:multi_get", 100: "client:read"}
    ops = dict(red.device_ops)
    assert ops["copy"] == pytest.approx(200e-9)


def test_no_window_means_nothing_to_read():
    assert reduce([ev(DEV, OPS_LINE, "copy", 0, 10)], NAMES) is None


def test_recorded_tpu_trace():
    events = json.loads((HERE / "data" / "trace_ycsb_c_tpu_v5e.json")
                        .read_text())
    red = reduce(events, NAMES)
    win = [e for e in events if e[2] == WINDOW_SPAN][0]
    assert red.window_s == pytest.approx(win[4] * 1e-9)
    # busy is the union of the device ops inside the window: at most the
    # plain sum of their clipped lengths, at least the longest one
    w0, w1 = win[3], win[3] + win[4]
    clipped = [min(e[3] + e[4], w1) - max(e[3], w0) for e in events
               if e[0].startswith(DEVICE_PREFIX) and e[1] == OPS_LINE
               and e[3] < w1 and e[3] + e[4] > w0]
    assert max(clipped) * 1e-9 <= red.busy_s <= sum(clipped) * 1e-9
    assert 0 < red.idle_share < 1
    # kernel time is the probe modules the name table matches, and only them
    probe = sum(e[4] for e in events if e[1] == MODULES_LINE
                and e[2].startswith("jit_rank_probe_pallas")
                and w0 <= e[3] < w1) * 1e-9
    assert probe > 0
    assert red.kernels["probe.rank"]["seconds"] == pytest.approx(probe)
    # the gaps and the busy time tile the window; gaps are named by spans
    hosts = {e[2] for e in events if not e[0].startswith(DEVICE_PREFIX)}
    assert {n for n, _ in red.idle_gaps} <= hosts | {"client:loop"}
    assert red.idle_gaps[0][0] == "store:multi_get"
    assert len(red.idle_gaps) == 10
    longest = [s for _, s in red.idle_gaps]
    assert longest == sorted(longest, reverse=True)


def test_op_names_lose_their_numbering():
    hlo = ("%rank_probe_pallas.1 = (s32[256,1]{1,0}) custom-call(u32[256,1]"
           "{1,0} %copy)")
    assert op_name(hlo) == "rank_probe_pallas"
    assert op_name("%copy-done = pred[256,1] copy-done(%x)") == "copy-done"
