"""The cell ``game-full-mf.cd-fit-ratings`` at the rehearsal size, on the
CPU: a sound run is ``correct``; the control (the reference at bfloat16, put
in the program's place), every other stand-in and every fault planted under
the timed path are not; the readers of the factored coordinate's spans give
what a hand-made context holds, and ``None`` where a program makes no such
span (the parent of PR 34).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_game_mf.py -q

The limits held here are ``traffic/cd-fit-ratings.tiny.json``'s; the cell's
own were read on the chip at the cell's size (PERF.md section 2).
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
from unittest import mock

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import compare, run as harness, work, work_mf  # noqa: E402
from benchmarks.tests import faults  # noqa: E402
from benchmarks.traffic import cd_fit, cd_fit_ratings  # noqa: E402

CELL = "game-full-mf.cd-fit-ratings.tiny"
MF = {m.NAME: m for m in harness.list_layer_metrics()
      if m.NAME.startswith("mf_") or m.NAME == "kron_map_roofline"}


def _run(capsys, seed=11, trace=0):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                       "--trace", str(trace)])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct_and_reports_the_factored_layer(capsys):
    result = _run(capsys, seed=3400000011, trace=1)
    assert result["correct"] is True, result["compared"]
    assert result["rehearsal"] is True and result["device"]["platform"] == "cpu"
    assert result["compared"]["repeat_gap"]["value"] == 0.0
    metrics = result["metrics"]
    # two alternations in each of a fit's two factored updates
    assert metrics["mf_matrix_evals.step"]["value"] >= 4
    assert metrics["mf_solve_s.step"]["value"] > 0
    assert 0 < metrics["mf_latent_live_lane_pct"]["value"] <= 100
    assert metrics["mf_compile_s.step"]["value"] == 0.0
    assert "kron_map_roofline" not in metrics  # a share of a peak: none on the CPU
    # the matrix solve is no glm/solve: the fixed effect's two solves a fit alone
    from photon_ml_tpu.telemetry import get_tracer

    spans = get_tracer().spans()
    fits = result["attempted"] + 1
    assert len([s for s in spans if s.name == "glm/solve"]) == 2 * fits
    assert len([s for s in spans if s.name == "mf/solve_matrix"]) == 4 * fits


@pytest.mark.parametrize("stand_in", cd_fit_ratings.STAND_INS)
def test_stand_in_is_not_correct(stand_in):
    """The control and each fault planted in the reference, put in the
    program's place, on three seeds."""
    w = harness.load_workload(CELL)
    config, traffic = w["config_doc"], w["traffic_doc"]
    for seed in (21, 22, 23):
        problem = cd_fit_ratings.make_problem(config, seed)
        kept = cd_fit_ratings.reference_run(config, traffic, problem)
        numbers = cd_fit_ratings.control_numbers(config, problem, *kept, stand_in=stand_in)
        ok, rows = compare.verdict(numbers, traffic["limits"])
        assert not ok, rows


@contextlib.contextmanager
def _frozen_matrix():
    """The factored coordinate's step (b) left out of the program: the
    projection matrix stays where it started."""
    from photon_ml_tpu.algorithm.factored_random_effect import FactoredRandomEffectCoordinate

    def keep(self, ds, latent_model, B, alternation=0):
        return B

    with mock.patch.object(FactoredRandomEffectCoordinate, "_solve_matrix", keep):
        yield


@pytest.mark.parametrize("fault", faults.FAULTS + (faults.CARRIED_OVER, "frozen_matrix"))
def test_planted_fault_is_not_correct(fault, capsys):
    # faults.planted tells a cd_fit driver by its module's name; this cell's
    # driver is a subclass of cd_fit's and takes the same faults
    planted = _frozen_matrix() if fault == "frozen_matrix" else faults.planted(fault, cd_fit)
    with planted:
        result = _run(capsys)
    assert result["correct"] is False, result["compared"]
    if fault == faults.CARRIED_OVER:
        assert result["compared"]["repeat_gap"]["value"] > 1e-3


# -- the readers, on a hand-made context ------------------------------------
def span(name, start, end, depth=1, **attrs):
    return {"name": name, "start": start, "end": end, "attrs": attrs, "depth": depth}


UNDER = "game/fit/cd/run/cd/outer_iter/cd/coordinate/mf/update/mf/solve_matrix"
BUCKETS = [{"entities": 16384, "samples": 100, "dim": 65}]
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# window [100, 120], two steps
HAND_MADE = [
    span("cd/coordinate", 101.0, 104.0, coordinate="per_user"),
    span("cd/coordinate", 104.0, 109.0, coordinate="user_item_mf"),
    span("mf/update", 104.5, 108.5, alternations=2, latent_factors=32),
    span("mf/solve_matrix", 105.0, 106.0, iterations=9, evaluations=12, coefficients=131072),
    span("mf/solve_matrix", 107.0, 108.5, iterations=7, evaluations=8, coefficients=131072),
    span("jit/trace", 105.1, 105.4, 3, fun_name="f", phase="trace", under=UNDER),
    span("jit/backend", 105.3, 105.6, 3, fun_name="f", phase="backend", under=UNDER),
    span("jit/trace", 101.5, 102.5, 3, fun_name="g", phase="trace",
         under="game/fit/cd/run/cd/outer_iter/cd/coordinate/re/train"),
    span("cd/coordinate", 112.0, 115.0, coordinate="user_item_mf"),
    span("mf/update", 112.5, 114.5, alternations=2, latent_factors=32),
    span("mf/solve_matrix", 113.0, 114.0, iterations=3, evaluations=4, coefficients=131072),
    # outside the window
    span("cd/coordinate", 90.0, 95.0, coordinate="user_item_mf"),
    span("mf/update", 90.5, 94.5, alternations=2, latent_factors=32),
    span("mf/solve_matrix", 91.0, 93.0, iterations=50, evaluations=60, coefficients=131072),
]
LANES = [
    {"coordinate": "per_user", "samples": 100, "dim": 16, "executed": 1000, "live": 500.0},
    {"coordinate": "user_item_mf", "samples": 100, "dim": 32, "executed": 400, "live": 300.0},
]


def context(spans, shapes=None, peaks=PEAKS):
    return {"window": (100.0, 120.0), "steps": 2, "window_s": 20.0, "spans": spans,
            "counters": [{"re_lanes": [dict(LANES[1], executed=10 ** 6)]},
                         {"re_lanes": LANES}, {"re_lanes": LANES}],
            "shapes": shapes or {"mf_buckets": BUCKETS, "mf_latent_factors": 32},
            "peaks": peaks, "trace": None, "times": {}}


def test_the_readers_of_the_factored_layer_on_a_hand_made_window():
    c = context(HAND_MADE)
    assert MF["mf_solve_s.step"].read(c) == pytest.approx((5.0 + 3.0) / 2)
    assert MF["mf_matrix_evals.step"].read(c) == (12 + 8 + 4) / 2
    # a union, never a sum: [105.1, 105.6]; the compile under re/train is not its
    assert MF["mf_compile_s.step"].read(c) == pytest.approx(0.5 / 2)
    assert MF["mf_latent_live_lane_pct"].read(c) == pytest.approx(75.0)
    flops, nbytes = work_mf.kron_evaluation(BUCKETS, 32)
    assert flops == 2 * 2.0 * 16384 * 100 * 65 * 32
    assert nbytes == 2 * 4.0 * (16384 * 100 * 65 + 16384 * 65 * 32)
    least, bound = work.least_seconds(24 * flops, 24 * nbytes, PEAKS)
    assert bound == "bytes"
    share = MF["kron_map_roofline"].read(c)
    assert share == pytest.approx(100.0 * least / 3.5)
    assert 0 < share < 100
    assert MF["kron_map_roofline"].read(context(HAND_MADE, peaks=None)) is None
    assert MF["kron_map_roofline"].read(context(HAND_MADE, shapes={"nnz": 1})) is None


def test_a_program_without_the_spans_gives_none():
    """The parent of PR 34: the same driver, no ``mf/*`` span."""
    c = context([s for s in HAND_MADE if not s["name"].startswith("mf/")])
    for name, reader in MF.items():
        assert reader.read(c) is None, name
