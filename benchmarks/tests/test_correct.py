"""``correct`` has to be able to fail. At the rehearsal size, on the CPU:

- a sound run of every rehearsal workload is correct;
- the control (the reference at bfloat16, put in the program's place) is not;
- the whole of a run with a fault planted under the timed path prints
  ``correct: false``.

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

REHEARSALS = ["glmix-1b-chip.cd-train.tiny", "fe-poisson-owlqn.refit.tiny"]


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


@pytest.mark.parametrize("stand_in", ["bfloat16", "half_batch", "unchanged"])
@pytest.mark.parametrize("workload", REHEARSALS)
def test_stand_in_is_not_correct(workload, stand_in):
    """The control (the reference at bfloat16) and each fault planted in the
    reference, put in the program's place, on three seeds."""
    from benchmarks import datagen

    w = harness.load_workload(workload)
    config, traffic = w["config_doc"], w["traffic_doc"]
    module = importlib.import_module(f"benchmarks.traffic.{traffic['driver']}")
    for seed in (21, 22, 23):
        problem = datagen.make_problem(config, seed)
        kept = module.reference_run(config, problem)
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
