#!/usr/bin/env python3
"""Checks of the yardstick's own arithmetic, on the CPU:

    python -m benchmarks.selfcheck

- the algorithmic-work counts (work.py) for a hand-sized matrix;
- the least-time rule and the table of peaks;
- the trace reduction (tracing.py) on a hand-made timeline with known
  answers, and on the small recorded trace under fixtures/ against a second,
  brute-force computation of the same numbers;
- the comparison's arithmetic (compare.py) on hand-sized leaves.

Exits 0 and prints "selfcheck ok", or raises.
"""

from __future__ import annotations

import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import compare, tracing, work  # noqa: E402


def close(a, b, tol=1e-9):
    assert abs(a - b) <= tol * max(1.0, abs(a), abs(b)), (a, b)


def check_work():
    # 3 rows x 4 columns, 5 nonzeros
    flops, nbytes = work.fe_evaluation(nnz=5, n_rows=3, n_cols=4)
    close(flops, 2 * 2 * 5)                       # two maps, 2 FLOPs a nonzero
    close(nbytes, 2 * 8 * 5 + 2 * 4 * (2 * 4 + 3))  # matrix twice; w, g, rows r+w
    flops, nbytes = work.fe_map(5, 3, 4)
    close(flops, 10)
    close(nbytes, 8 * 5 + 4 * 7)
    flops, nbytes = work.re_lane_iteration(samples=10, dim=16)
    close(flops, 4 * 160)
    close(nbytes, 4 * 160)
    peaks = work.load_peaks("TPU v5 lite")
    close(peaks["flops_per_s"], 197e12)
    close(peaks["hbm_bytes_per_s"], 819e9)
    least, bound = work.least_seconds(197e12, 819e9 * 2, peaks)
    close(least, 2.0)
    assert bound == "bytes"
    try:
        work.load_peaks("no such chip")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device must be an error")
    # one step of two solves: 6 + 4 evaluations, 3 Hessian-vector products
    solves = [{"iterations": 5, "evaluations": 6, "hessian_vecs": 0},
              {"iterations": 3, "evaluations": 4, "hessian_vecs": 3}]
    lanes = [{"samples": 10, "dim": 16, "live": 3.0, "executed": 8}]
    assert work.fe_maps(solves) == 2 * (6 + 4 + 3)
    flops, nbytes = work.step_work({"nnz": 5, "n_rows": 3, "n_cols": 4}, solves, lanes)
    close(flops, 10 * 20 + 2 * 3 * 10 + 3 * 640)
    close(nbytes, 10 * (80 + 88) + 2 * 3 * (40 + 28) + 3 * 640)


def check_trace_by_hand():
    # device 0: [0,10) busy, a while [20,60) that spans two kernels
    # [25,35) and [40,50); window [0,100)
    events = [
        {"device": 0, "name": "fusion.1", "start_ns": 0.0, "dur_ns": 10.0},
        {"device": 0, "name": "while.2", "start_ns": 20.0, "dur_ns": 40.0},
        {"device": 0, "name": "_base_call", "start_ns": 25.0, "dur_ns": 10.0},
        {"device": 0, "name": "_base_call", "start_ns": 40.0, "dur_ns": 10.0},
        {"device": 0, "name": "late", "start_ns": 95.0, "dur_ns": 20.0},  # clipped at 100
    ]
    r = tracing.reduce_trace(events, (0.0, 100.0), devices=1)
    close(r["busy_s"], (10 + 40 + 5) / 1e9)
    close(r["window_s"], 100 / 1e9)
    close(r["idle_share"], 0.45)
    close(r["self_times"]["while"], 20 / 1e9)
    close(r["self_times"]["_base_call"], 20 / 1e9)
    hlo = ('%_lambda_.101 = f32[16384,8,128]{2,1,0:T(8,128)} custom-call(f32[131072,128]{1,0} %bitcast.388, '
           's8[131072,128]{1,0} %dd_features_blocks_4__plan_inv_idx_1_.1), custom_call_target="tpu_custom_call"')
    assert tracing.group_name(hlo) == "pallas:routed_map_kernel"
    assert tracing.group_name(hlo.replace("s8[", "f32[")) == "pallas:_lambda_"
    assert tracing.group_name("%multiply_reduce_fusion.34 = f32[40000000]{0:T(1024)} fusion(f32[10,4]{1,0} %x)") \
        == "multiply_reduce_fusion"
    assert tracing.group_name("%add.822 = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)") == "add"
    assert tracing.group_name("%copy.3 = (f32[2]{0}, u32[]) copy-start(f32[2]{0} %c)") == "copy (copy-start)"
    gaps = dict(tracing.idle_gaps(
        r["busy_intervals"], (0.0, 100.0),
        [(0.0, 100.0, "outer"), (8.0, 22.0, "compile")],
    ))
    close(gaps["compile"], 10 / 1e9)   # the gap [10,20), all of it under the compile
    close(gaps["outer"], 35 / 1e9)     # the gap [60,95)


def check_trace_fixture():
    path = os.path.join(HERE, "fixtures", "recorded_trace.json")
    with open(path) as f:
        doc = json.load(f)
    events, window = doc["events"], tuple(doc["window_ns"])
    r = tracing.reduce_trace(events, window, devices=1)
    # brute force: sweep over the sorted end points
    points = sorted({window[0], window[1]} | {
        min(max(p, window[0]), window[1])
        for e in events for p in (e["start_ns"], e["start_ns"] + e["dur_ns"])
    })
    busy = 0.0
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        if any(e["start_ns"] <= mid < e["start_ns"] + e["dur_ns"] for e in events):
            busy += b - a
    close(r["busy_s"], busy / 1e9, 1e-6)
    close(sum(r["self_times"].values()), busy / 1e9, 1e-6)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    # the recorded window holds routed-map kernels, told by their int8 operand
    assert r["self_times"]["pallas:routed_map_kernel"] > 0
    assert doc["device_kind"] and doc["recorded_by"]


def check_compare():
    import numpy as np

    ref = {"a": np.array([3.0, 4.0]), "b": np.array([0.0, 1e-6]), "c": np.array([1.0, 0.0])}
    prog = {"a": np.array([3.0, 4.1]), "b": np.array([0.0, 0.0]), "c": np.array([1.0, 0.0])}
    # median leaf norm is 1: leaf b is held against it, not against 1e-6
    gap = compare.worst_leaf_norm_gap(prog, ref)
    close(gap, (math.hypot(3, 4.1) - 5.0) / 5.0)
    # a fit from nothing over three coordinates: updates 0 and 1 hold no full model
    assert compare.picked_update([0.9, 0.8, 0.7], complete_from=2) == 2
    assert compare.picked_update([0.9, 0.8, 0.7, 0.75, 0.75, 0.6], complete_from=2) == 3
    assert compare.picked_update([0.7, 0.8, 0.8], complete_from=0) == 1
    close(compare.repeat_gap([{"objective": [4.0, 2.0]}, {"objective": [4.0, 2.0]},
                              {"objective": [4.0, 2.5]}])["repeat_gap"], 0.25)
    assert compare.repeat_gap([{"objective": [4.0, 2.0]}, {"objective": [4.0]}])["repeat_gap"] == math.inf
    ok, rows = compare.verdict({"x": 1.0, "y": float("nan")}, {"x": 2.0, "y": 1.0})
    assert not ok
    ok, _ = compare.verdict({"x": 1.0}, {"x": 2.0})
    assert ok
    ok, _ = compare.verdict({}, {"x": 2.0})
    assert not ok


def main() -> int:
    check_work()
    check_trace_by_hand()
    check_trace_fixture()
    check_compare()
    print("selfcheck ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
