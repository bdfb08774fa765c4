"""The cell ``fe-linear-tron.refit-norm`` at its rehearsal size, on the CPU:
a sound run is correct; the control (the reference at bfloat16) and each
stand-in and planted fault are not; the readers this cell brought
(``fe_hvs.step``, ``summarize_s``) and the maps' roofline counting its
Hessian-vector products (``sparse_map_roofline``) on a hand-made ``context``,
and silent on what a program without the counts hands over.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_refit_norm.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import compare, run as harness, work  # noqa: E402
from benchmarks.tests import faults  # noqa: E402
from benchmarks.traffic import refit_norm  # noqa: E402

REHEARSAL = "fe-linear-tron.refit-norm.tiny"
NEW = ("fe_hvs.step", "sparse_map_roofline", "summarize_s")
ONLY_HERE = ("fe_hvs.step", "summarize_s")
READERS = {m.NAME: m for m in harness.list_layer_metrics() if m.NAME in NEW}


def _run(capsys, trace=0, seed=2800000011):
    rc = harness.main(
        ["--workload", REHEARSAL, "--seed", str(seed), "--seconds", "1", "--trace", str(trace)])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct_and_reports_the_program_side_metrics(capsys):
    result = _run(capsys, trace=1)
    assert result["correct"] is True, result["compared"]
    assert result["rehearsal"] is True and result["device"]["platform"] == "cpu"
    metrics = result["metrics"]
    assert {"fe_hvs.step", "summarize_s", "fe_evals.step", "fe_iterations.step",
            "fe_solve_s.step", "routing_prep_s"} <= set(metrics)
    assert "sparse_map_roofline" not in metrics  # a device metric: never from a CPU
    assert metrics["fe_hvs.step"]["value"] >= metrics["fe_iterations.step"]["value"] >= 1
    assert metrics["fe_evals.step"]["value"] == metrics["fe_iterations.step"]["value"] + 1
    assert metrics["fe_compile_s.step"]["value"] == 0.0  # no compile inside the window


@pytest.mark.parametrize("stand_in", refit_norm.STAND_INS)
def test_stand_in_is_not_correct(stand_in):
    w = harness.load_workload(REHEARSAL)
    config, traffic, limits = w["config_doc"], w["traffic_doc"], w["traffic_doc"]["limits"]
    for seed in (21, 22, 23):
        problem = refit_norm.make_problem(config, seed)
        kept = refit_norm.reference_run(config, traffic, problem)
        numbers = refit_norm.control_numbers(config, problem, *kept, stand_in=stand_in)
        ok, rows = compare.verdict(numbers, limits)
        assert not ok, rows


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_not_correct(fault, capsys):
    with faults.planted(fault, refit_norm):
        result = _run(capsys)
    assert result["correct"] is False, result["compared"]


def span(name, start, end, **attrs):
    return {"name": name, "start": start, "end": end, "attrs": attrs, "depth": 1}


SHAPES = {"nnz": 1 << 24, "n_rows": 1 << 20, "n_cols": 40_000_000}
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# window [100, 110], two steps; the warm-up fit and the statistics lie before it
RECORDED = [
    span("glm/summarize", 40.0, 47.5, columns=40_000_000, engine="ColumnSplitFeatures"),
    span("glm/solve", 90.0, 95.0, iterations=5, evaluations=6, hessian_vecs=21, rejected_steps=0),
    span("glm/solve", 100.0, 105.0, iterations=5, evaluations=6, hessian_vecs=21, rejected_steps=0),
    span("glm/solve", 105.0, 110.0, iterations=6, evaluations=7, hessian_vecs=24, rejected_steps=1),
]


def context(spans, kernels_s=6.0):
    trace = {"self_times": {"pallas:routed_map_kernel": kernels_s, "fusion": 1.0}}
    return {"window": (100.0, 110.0), "steps": 2, "window_s": 10.0, "spans": spans,
            "counters": [{}] * 3, "shapes": SHAPES, "times": {}, "trace": trace, "peaks": PEAKS}


def test_readers_on_a_recorded_context():
    ctx = context(RECORDED)
    assert READERS["fe_hvs.step"].read(ctx) == (21 + 24) / 2
    assert READERS["summarize_s"].read(ctx) == 7.5
    maps = 2 * (6 + 21) + 2 * (7 + 24)
    _, nbytes = work.fe_map(**SHAPES)
    share = READERS["sparse_map_roofline"].read(ctx)
    assert share == pytest.approx(100.0 * maps * nbytes / 819e9 / 6.0)
    assert 0 < share < 100
    # three kernel calls a product where the count has two: the share falls, never rises
    assert READERS["sparse_map_roofline"].read(context(RECORDED, kernels_s=9.0)) < share


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_without_the_counts(name):
    """What the parent commit hands over: ``glm/solve`` spans without
    ``hessian_vecs``, no ``glm/summarize``; and a trace without the kernels."""
    old = [span("glm/solve", 100.0, 105.0, iterations=5, evaluations=6)]
    if name in ONLY_HERE:
        assert READERS[name].read(context(old)) is None
    assert READERS[name].read(context([])) is None
    if name == "sparse_map_roofline":
        renamed = dict(context(RECORDED), trace={"self_times": {"fusion": 1.0}})
        assert READERS[name].read(renamed) is None
        assert READERS[name].read(dict(context(RECORDED), trace=None)) is None


def test_every_new_metric_has_an_entry_for_this_cell_alone():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert set(READERS) == set(NEW)
    for name in ONLY_HERE:
        entry, reader = declared[name], READERS[name]
        assert (entry["unit"], entry["source"]) == (reader.UNIT, reader.SOURCE)
        assert entry["workloads"] == ["fe-linear-tron.refit-norm"]
    assert "workloads" not in declared["sparse_map_roofline"]
