"""``correct`` has to be able to fail. At the rehearsal size, on the CPU:

- a sound run of every rehearsal workload is correct;
- the control (the reference at bfloat16, put in the program's place) is not;
- the whole of a run with a fault planted under the timed path prints
  ``correct: false``; a ``cd-fit`` fit that starts from its predecessor's
  model fails ``repeat_gap``;
- the ``cd-fit`` rehearsal's fits are the same work: identical objectives
  and solver counts, fit for fit.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

The limits held here are the rehearsal traffic files' (``*.tiny.json``); the
cells' own limits were read on the chip at the cells' size (PERF.md).
"""

from __future__ import annotations

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import compare, run as harness  # noqa: E402
from benchmarks.tests import faults  # noqa: E402

CD_FIT = "glmix-1b-chip.cd-fit.tiny"
REHEARSALS = [CD_FIT, "fe-poisson-owlqn.refit.tiny"]


def _run(workload, capsys, seed=11):
    rc = harness.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", "1", "--trace", "0"]
    )
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", REHEARSALS)
def test_sound_run_is_correct(workload, capsys):
    result = _run(workload, capsys)
    assert result["correct"] is True, result["compared"]
    assert result["rehearsal"] is True and result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload,stand_in", [
    (w, s) for w in REHEARSALS for s in ("bfloat16", "half_batch", "unchanged")
] + [(CD_FIT, "carried_over")])
def test_stand_in_is_not_correct(workload, stand_in):
    """The control (the reference at bfloat16) and each fault planted in the
    reference, put in the program's place, on three seeds."""
    w = harness.load_workload(workload)
    config, traffic = w["config_doc"], w["traffic_doc"]
    module = importlib.import_module(f"benchmarks.traffic.{traffic['driver']}")
    assert stand_in in module.STAND_INS
    for seed in (21, 22, 23):
        problem = module.make_problem(config, seed)
        kept = module.reference_run(config, traffic, problem)
        numbers = module.control_numbers(config, problem, *kept, stand_in=stand_in)
        ok, rows = compare.verdict(numbers, traffic["limits"])
        assert not ok, rows


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("workload", REHEARSALS)
def test_planted_fault_is_not_correct(workload, fault, capsys):
    w = harness.load_workload(workload)
    module = importlib.import_module(f"benchmarks.traffic.{w['traffic_doc']['driver']}")
    with faults.planted(fault, module):
        result = _run(workload, capsys)
    assert result["correct"] is False, result["compared"]


def test_a_fit_that_starts_from_its_predecessors_model_fails_repeat_gap(capsys):
    from benchmarks.traffic import cd_fit

    with faults.planted(faults.CARRIED_OVER, cd_fit):
        result = _run(CD_FIT, capsys)
    assert result["correct"] is False, result["compared"]
    repeat = result["compared"]["repeat_gap"]
    assert repeat["value"] > 1e-3 > repeat["limit"]


def test_cd_fit_rehearsal_runs_the_same_fit_again_and_again(capsys):
    """Three fits or more (the warm-up and the window's), six objectives
    each, identical fit for fit, and so are what the solver counted on the
    ``glm/solve`` spans: two solves a fit."""
    from photon_ml_tpu.telemetry import get_tracer

    rc = harness.main(["--workload", CD_FIT, "--seed", "3300000011", "--seconds", "8",
                       "--trace", "1"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["repeat_gap"]["value"] == 0.0
    fits = result["attempted"] + 1
    assert fits >= 3
    solves = [s for s in get_tracer().spans() if s.name == "glm/solve"]
    assert len(solves) == 2 * fits
    counts = [(s.attrs["iterations"], s.attrs["evaluations"]) for s in solves]
    assert all(counts[2 * i:2 * i + 2] == counts[:2] for i in range(fits))
    metrics = result["metrics"]
    assert metrics["fe_evals.step"]["value"] == counts[0][1] + counts[1][1]
    assert metrics["fe_iterations.step"]["value"] == counts[0][0] + counts[1][0]
