"""The readers of the program's compile spans and of the spans that cover a
step (``layer_metrics/`` files added by PR 26), on a hand-made ``context``
and in the two rehearsals; and the readers that count a step's work from the
solver's own spans (``fe_iterations.step``, ``fe_evals.step``,
``sparse_map_roofline``, ``step_mfu`` through ``work.step_work``):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_span_metrics.py -q

Times are unions of intervals cut to the window, never sums; a program
without the spans (any commit before PR 26) gives ``None``.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import run as harness  # noqa: E402

NEW = {
    "retrace_s.step", "lower_s.step", "backend_compile_s.step", "cache_misses.step",
    "fe_compile_s.step", "fe_evals.step", "re_compile_s.step", "re_round_wait_s.step",
    "cd_compile_s.step", "step_unattributed_s.step",
}
ONLY_CD_FIT = {"re_compile_s.step", "re_round_wait_s.step", "cd_compile_s.step"}
READERS = {m.NAME: m for m in harness.list_layer_metrics() if m.NAME in NEW}


def span(name, start, end, depth=1, **attrs):
    return {"name": name, "start": start, "end": end, "attrs": attrs, "depth": depth}


def jit(phase, start, end, under, fun_name="f"):
    return span(f"jit/{phase}", start, end, 3, fun_name=fun_name, phase=phase, under=under)


def context(spans, window=(100.0, 120.0), steps=2):
    return {"window": window, "steps": steps, "window_s": window[1] - window[0],
            "spans": spans, "counters": [{}] * (steps + 1)}


FE = "game/fit/cd/run/cd/outer_iter/cd/coordinate/fe/solve/glm/train/glm/solve"
RE = "game/fit/cd/run/cd/outer_iter/cd/coordinate/re/train/re/adaptive_round"
CD = "game/fit/cd/run/cd/initial_scores"

# window [100, 120], two steps
HAND_MADE = [
    span("game/prepare_fit", 99.0, 100.5),        # clipped at the window's start: 0.5
    span("cd/initial_scores", 100.5, 103.0),
    span("cd/outer_iter", 103.0, 118.0),
    span("cd/coordinate", 103.0, 110.0, coordinate="fixed"),
    span("glm/train", 103.5, 109.0),
    span("glm/solve", 103.6, 108.9, iterations=9, evaluations=14),
    span("cd/coordinate", 110.0, 116.0, coordinate="per_user"),
    span("re/train", 110.0, 115.9),
    span("re/round_wait", 111.0, 111.25),
    span("re/round_wait", 119.5, 121.0),          # clipped at the window's end: 0.5
    span("cd/objective", 116.0, 117.0),
    span("cd/validate", 117.0, 118.0),            # 118 - 120 is covered by nothing
    jit("trace", 99.0, 101.0, "game/prepare_fit"),   # clipped: 1.0 inside
    jit("trace", 101.0, 102.0, CD),
    jit("lower", 102.0, 102.5, CD),
    jit("backend", 102.5, 102.75, CD),
    jit("trace", 104.0, 106.0, FE),
    jit("lower", 106.0, 108.0, FE),
    jit("trace", 106.5, 107.5, FE, "kernel_body"),   # a trace inside the lowering, another thread's
    jit("backend", 108.0, 108.5, FE),
    jit("trace", 112.0, 113.0, RE),
    jit("backend", 113.0, 113.5, RE),
    jit("backend", 119.0, 123.0, RE),             # clipped: 1.0 inside
    span("jit/cache", 108.0, 108.0, 3, hit=True, under=FE),
    span("jit/cache", 113.0, 113.0, 3, hit=False, under=RE),
    span("jit/cache", 119.0, 119.0, 3, hit=False, under=RE),
    span("jit/cache", 90.0, 90.0, 3, hit=False, under=""),   # before the window
]
EXPECTED = {
    "retrace_s.step": (1.0 + 1.0 + 2.0 + 1.0 + 1.0) / 2,
    "lower_s.step": (0.5 + 2.0) / 2,
    "backend_compile_s.step": (0.25 + 0.5 + 0.5 + 1.0) / 2,
    "cache_misses.step": 2 / 2,
    # [104, 108.5] once: the nested trace is not counted again
    "fe_compile_s.step": 4.5 / 2,
    "fe_evals.step": 14 / 2,
    "re_compile_s.step": (1.5 + 1.0) / 2,
    "re_round_wait_s.step": (0.25 + 0.5) / 2,
    "cd_compile_s.step": (1.0 + 1.75) / 2,
    "step_unattributed_s.step": 2.0 / 2,
}


def test_every_new_metric_has_a_reader_and_an_entry():
    assert set(READERS) == NEW
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, reader in READERS.items():
        entry = declared[name]
        assert (entry["unit"], entry["source"]) == (reader.UNIT, reader.SOURCE)
        assert entry["moves"] == "train_step_s" and entry["better"] == "lower"
        assert ("workloads" in entry) == (name in ONLY_CD_FIT)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_a_hand_made_context(name):
    assert READERS[name].read(context(HAND_MADE)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_finds_nothing_in_a_program_without_the_spans(name):
    """What the parent commit hands over: its own spans, none of PR 26's."""
    old = [s for s in HAND_MADE if not s["name"].startswith(("jit/", "glm/", "game/prepare"))
           and s["name"] not in ("cd/initial_scores", "re/round_wait")]
    assert old and READERS[name].read(context(old)) is None
    assert READERS[name].read(context([])) is None


def test_the_accounting_closes():
    """By site and by phase the same compile spans are split; neither split
    counts an interval twice, so each sums to the union of all of them, or
    over it where a phase runs inside another."""
    ctx = context(HAND_MADE)
    by_site = sum(EXPECTED[n] for n in ("fe_compile_s.step", "re_compile_s.step", "cd_compile_s.step"))
    by_phase = sum(EXPECTED[n] for n in ("retrace_s.step", "lower_s.step", "backend_compile_s.step"))
    from benchmarks.layer_metrics import _compile

    union = _compile.union_per_step(ctx, _compile.compile_spans(ctx))
    assert by_site == pytest.approx(union)
    assert by_phase == pytest.approx(union + 1.0 / 2)   # the trace inside the lowering
    assert by_phase <= ctx["window_s"] / ctx["steps"]


def test_a_window_without_compiles_reads_zero_not_none():
    """Once the program makes compile spans at all, a window that holds none
    is a measurement (a step that no longer re-traces), not a silence."""
    spans = [jit("trace", 10.0, 11.0, "warm-up"), span("glm/train", 100.0, 120.0),
             span("glm/solve", 100.0, 120.0, iterations=3, evaluations=4)]
    ctx = context(spans)
    for name in ("retrace_s.step", "lower_s.step", "backend_compile_s.step",
                 "cache_misses.step", "fe_compile_s.step", "step_unattributed_s.step"):
        assert READERS[name].read(ctx) == 0.0
    for name in ONLY_CD_FIT:
        assert READERS[name].read(ctx) is None


@pytest.mark.parametrize("workload,silent,window_compiles", [
    # the cd-fit rehearsal's window holds compiles (the Pallas interpreter
    # traces as it runs); a refit's holds none since PR 27
    ("glmix-1b-chip.cd-fit.tiny", set(), True),
    ("fe-poisson-owlqn.refit.tiny", ONLY_CD_FIT, False),
])
def test_rehearsal_reports_every_new_metric(workload, silent, window_compiles, capsys):
    rc = harness.main(["--workload", workload, "--seed", "2600000011", "--seconds", "1", "--trace", "1"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    metrics = result["metrics"]
    assert result["correct"]
    assert NEW - silent <= set(metrics)
    assert not silent & set(metrics)
    steps = result["attempted"]
    assert metrics["fe_evals.step"]["value"] >= metrics["fe_iterations.step"]["value"] + 1
    compile_union = metrics["fe_compile_s.step"]["value"] + sum(
        metrics[n]["value"] for n in ("re_compile_s.step", "cd_compile_s.step") if n in metrics)
    by_phase = sum(metrics[n]["value"] for n in ("retrace_s.step", "lower_s.step", "backend_compile_s.step"))
    assert by_phase >= compile_union * (1 - 1e-9) >= 0
    assert (compile_union > 0) == window_compiles
    assert steps >= 1


# -- a step's work, counted from the solver's own spans (PR 33) ---------------
SHAPES = {"nnz": 1 << 24, "n_rows": 1 << 20, "n_cols": 40_000_000}
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LANE = {"coordinate": "per_user", "samples": 100, "dim": 16, "executed": 4096, "live": 3000.0}
# window [100, 120]: one step (a fit) of two solves; the warm-up fit's lie before it
TWO_SOLVES = [
    span("glm/solve", 80.0, 85.0, iterations=8, evaluations=10, hessian_vecs=0),
    span("glm/solve", 90.0, 93.0, iterations=5, evaluations=7, hessian_vecs=0),
    span("glm/solve", 100.0, 105.0, iterations=8, evaluations=10, hessian_vecs=0),
    span("glm/solve", 110.0, 113.0, iterations=5, evaluations=7, hessian_vecs=3),
]


def work_context(spans, kernels_s=2.0):
    trace = {"self_times": {"pallas:routed_map_kernel": kernels_s, "fusion": 1.0}}
    return {"window": (100.0, 120.0), "steps": 1, "window_s": 20.0, "spans": spans,
            "counters": [{"re_lanes": [LANE]}, {"re_lanes": [LANE]}], "shapes": SHAPES,
            "times": {}, "trace": trace, "peaks": PEAKS}


def test_step_work_counts_the_evaluations_and_products_of_two_solves_in_one_step():
    from benchmarks import work

    by_name = {m.NAME: m for m in harness.list_layer_metrics()}
    ctx = work_context(TWO_SOLVES)
    assert by_name["fe_iterations.step"].read(ctx) == 8 + 5
    assert by_name["fe_evals.step"].read(ctx) == 10 + 7
    assert by_name["fe_hvs.step"].read(ctx) == 3
    solves = [s["attrs"] for s in TWO_SOLVES[2:]]
    assert work.fe_maps(solves) == 2 * (10 + 7 + 3)
    flops, nbytes = work.step_work(SHAPES, solves, [LANE])
    ef, eb = work.fe_evaluation(**SHAPES)
    mf, mb = work.fe_map(**SHAPES)
    lf, lb = work.re_lane_iteration(100, 16)
    assert flops == pytest.approx(17 * ef + 2 * 3 * mf + 3000.0 * lf)
    assert nbytes == pytest.approx(17 * eb + 2 * 3 * mb + 3000.0 * lb)
    assert by_name["step_mfu"].read(ctx) == pytest.approx(100.0 * nbytes / 819e9 / 20.0)
    share = by_name["sparse_map_roofline"].read(ctx)
    assert share == pytest.approx(100.0 * 40 * mb / 819e9 / 2.0)
    assert 0 < share < 100
    # a third kernel call a product where the count has two: the share falls, never rises
    assert by_name["sparse_map_roofline"].read(work_context(TWO_SOLVES, kernels_s=3.0)) < share


@pytest.mark.parametrize("name", ["fe_iterations.step", "fe_evals.step", "sparse_map_roofline"])
def test_work_readers_are_silent_without_the_solvers_counts(name):
    by_name = {m.NAME: m for m in harness.list_layer_metrics()}
    bare = [span("glm/solve", 100.0, 105.0)]     # an untraced solve sets no attrs
    assert by_name[name].read(work_context(bare)) is None
    assert by_name[name].read(work_context([])) is None
    if name == "sparse_map_roofline":
        renamed = dict(work_context(TWO_SOLVES), trace={"self_times": {"fusion": 1.0}})
        assert by_name[name].read(renamed) is None
        assert by_name[name].read(dict(work_context(TWO_SOLVES), trace=None)) is None


def test_retired_metrics_are_gone():
    names = {m.NAME for m in harness.list_layer_metrics()}
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        doc = json.load(f)
    declared = {m["name"] for m in doc["per_layer"]}
    assert names == declared
    assert not {"compile_s.step", "programs.step", "hv_map_roofline"} & names
    cells = {w["name"] for w in doc["workloads"]}
    for m in doc["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
