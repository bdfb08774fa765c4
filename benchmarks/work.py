"""Algorithmic work: the operations and bytes a step needs whatever
implements it, from shapes and the solvers' own counts, and the least time
the chip could take for them.

One fixed-effect objective evaluation reads the matrix twice (matvec and
rmatvec) at 8 bytes a nonzero (a float32 value and an int32 column id), and
reads and writes the coefficient vector, the gradient and the row vector
once each; 2 FLOPs a nonzero a map. A Hessian-vector product is two maps.
Both are counted by the solver where they happen and reach the benchmark on
the program's ``glm/solve`` spans. One random-effect lane-iteration reads
its [samples, dim] float32 block once and spends 4 FLOPs an element (margin
and gradient). Nothing of the routed network's padding, slots or passes is
counted: that is this implementation's cost, not the algorithm's.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmarks/peaks.json "
            f"(have {sorted(table)}); add its published peaks with their source"
        )
    return table[device_kind]


def fe_evaluation(nnz: int, n_rows: int, n_cols: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one value-and-gradient of a sparse GLM."""
    flops = 2 * 2.0 * nnz
    matrix = 2 * 8.0 * nnz
    vectors = 2 * 4.0 * (2 * n_cols + n_rows)  # w, g, row vector: read + written
    return flops, matrix + vectors


def fe_map(nnz: int, n_rows: int, n_cols: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one matvec or rmatvec alone: the matrix once, its
    input vector read and its output vector written."""
    return 2.0 * nnz, 8.0 * nnz + 4.0 * (n_rows + n_cols)


def re_lane_iteration(samples: int, dim: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one entity's solver iteration."""
    elements = float(samples) * dim
    return 4.0 * elements, 4.0 * elements


def least_seconds(flops: float, nbytes: float, peaks: Dict[str, float]) -> Tuple[float, str]:
    """The least time the chip needs, and which peak bounds it."""
    by_flops = flops / peaks["flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")


def fe_maps(solves: list) -> int:
    """Sparse maps the algorithm needs for the given fixed-effect solves
    (the attrs of the program's ``glm/solve`` spans): two a value-and-gradient
    evaluation (``evaluations``: counted by the solver in its loop carry,
    line-search and projection retries included) and two a Hessian-vector
    product (``hessian_vecs``: TRON's CG steps)."""
    return sum(2 * (int(a["evaluations"]) + int(a.get("hessian_vecs") or 0)) for a in solves)


def step_work(shapes: dict, solves: list, lanes: list) -> Tuple[float, float]:
    """(FLOPs, bytes) of the steps whose fixed-effect solves (``glm/solve``
    attrs) and random-effect lanes (the driver's ``re_lanes`` counters) are
    given: ``fe_evaluation`` an evaluation, two ``fe_map`` a Hessian-vector
    product, ``re_lane_iteration`` a live lane-iteration. What the program
    does beyond the algorithm's count (a third map a product where it
    computes the margins again, the score plane's matvecs, validation) is
    in the time and not here, so a share of peak built on this reads low,
    never high."""
    flops = nbytes = 0.0
    dims = (shapes["nnz"], shapes["n_rows"], shapes["n_cols"])
    for a in solves:
        f, b = fe_evaluation(*dims)
        flops += int(a["evaluations"]) * f
        nbytes += int(a["evaluations"]) * b
        f, b = fe_map(*dims)
        flops += 2 * int(a.get("hessian_vecs") or 0) * f
        nbytes += 2 * int(a.get("hessian_vecs") or 0) * b
    for lane in lanes:
        f, b = re_lane_iteration(lane["samples"], lane["dim"])
        flops += lane["live"] * f
        nbytes += lane["live"] * b
    return flops, nbytes
