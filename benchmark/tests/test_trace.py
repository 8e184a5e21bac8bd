"""The trace reduction, on a hand-made trace and on a recorded one."""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import trace  # noqa: E402

MS = 1_000_000  # ns


def test_union_idle_and_gaps():
    tr = {"spans": [["bench.window", 0, 100 * MS],
                    ["bench.walk", 0, 100 * MS],
                    ["bench.fetch", 20 * MS, 30 * MS]],
          "devices": [{"name": "/device:TPU:0",
                       "ops": [["_sig_kernel", 10 * MS, 10 * MS],
                               ["_msm_kernel", 15 * MS, 10 * MS],  # overlaps
                               ["_miller_grid_kernel", 60 * MS, 20 * MS],
                               ["late", 95 * MS, 10 * MS]],  # clipped
                       "modules": [["jit__sig_pl", 10 * MS, 15 * MS],
                                   ["jit__miller_grid", 60 * MS, 20 * MS]]}]}
    red = trace.reduce(tr)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.040)
    # busy [10,25) [60,80) [95,100): the gaps, longest first
    assert [round(g, 6) for _, g in red["idle_gaps"]] == [0.035, 0.015, 0.01]
    assert red["idle_gaps"][0][0] == "bench.fetch"   # midpoint 42.5 ms
    assert red["idle_gaps"][1][0] == "bench.walk"    # midpoint 87.5 ms
    assert red["per_op_s"]["/late"] == pytest.approx(0.010)
    # ops inside a program event count to that program
    assert red["per_program_s"]["jit__sig_pl"] == pytest.approx(0.020)
    assert trace.family_seconds(red, [r"_sig_pl", r"_miller_grid"]) \
        == pytest.approx(0.040)
    assert trace.family_seconds(red, [r"nothing"]) is None


def test_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"spans": [["bench.window", 0, MS]], "devices": []})


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(BENCH, "tests", "data", "*.trace.json.gz"))))
def test_recorded_trace(path):
    """A trace extracted from a traced chip run: the reduction's busy time
    equals a direct sweep over its op events, and lies in the window."""
    with gzip.open(path, "rt") as f:
        tr = json.load(f)
    red = trace.reduce(tr)
    w0, w1 = next((s, s + d) for n, s, d in tr["spans"]
                  if n == "bench.window")
    dev = [d for d in tr["devices"] if d["ops"]][0]
    edges = sorted((max(s, w0), min(s + d, w1)) for _, s, d in dev["ops"]
                   if s < w1 and s + d > w0)
    busy, end = 0, None
    for s, e in edges:
        if end is None or s >= end:
            busy, end = busy + e - s, e
        elif e > end:
            busy, end = busy + e - end, e
    assert red["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < red["busy_s"] <= red["window_s"]
