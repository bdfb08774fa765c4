"""The cell ``glmix-members-1m.cd-fit-members`` at the rehearsal size, on
the CPU: a sound run is ``correct``; the control (the reference at bfloat16,
put in the program's place) and every stand-in the driver module lists are
not; the planted faults under the timed path are not; the two readers of
the random effects' state and fill give what a hand-made context holds, and
``None`` where a program's spans carry no such attrs (a program older than them).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_members.py -q

The limits held here are ``traffic/cd-fit-members.tiny.json``'s; the
cell's own were read on the chip at the cell's size (PERF.md section 2).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import compare, datagen_members, run as harness  # noqa: E402
from benchmarks.tests import faults  # noqa: E402
from benchmarks.traffic import cd_fit, cd_fit_members  # noqa: E402

CELL = "glmix-members-1m.cd-fit-members.tiny"
READERS = {m.NAME: m for m in harness.list_layer_metrics()
           if m.NAME in ("re_state_bytes_per_entity", "re_fill_pct")}


def _run(capsys, seed=4300000011, trace=0):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                       "--trace", str(trace)])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_every_member_has_a_row_and_the_rows_follow_the_power_law():
    w = harness.load_workload(CELL)
    config = w["config_doc"]
    problem = datagen_members.make_problem(config, 4300000012)
    re = config["random_effects"]["per_member"]
    counts = np.bincount(problem.train.entities["per_member"], minlength=re["n_entities"])
    assert counts.size == re["n_entities"] and counts.min() >= 1
    assert counts.max() <= re["rows_per_member"][1]
    assert counts.sum() == config["n_rows"] == problem.train.n
    assert 0.55 < np.mean(counts == 1) < 0.7
    # the pattern is the pattern seed's: another --seed draws the same members
    again = datagen_members.make_problem(config, 4300000013)
    np.testing.assert_array_equal(again.train.entities["per_member"],
                                  problem.train.entities["per_member"])


def test_sound_run_is_correct_and_reports_the_new_layers(capsys):
    result = _run(capsys, trace=1)
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["repeat_gap"]["value"] == 0.0
    metrics = result["metrics"]
    # the CPU lays the [E, 10, 16] histories out unpadded: 1,560 bytes an entity
    assert 1000 < metrics["re_state_bytes_per_entity"]["value"] < 2048
    assert 50 < metrics["re_fill_pct"]["value"] <= 100


@pytest.mark.parametrize("stand_in", cd_fit_members.STAND_INS)
def test_stand_in_is_not_correct(stand_in):
    """The control and each fault planted in the reference, put in the
    program's place, on three seeds."""
    w = harness.load_workload(CELL)
    config, traffic = w["config_doc"], w["traffic_doc"]
    for seed in (4300000021, 4300000022, 4300000023):
        problem = cd_fit_members.make_problem(config, seed)
        kept = cd_fit_members.reference_run(config, traffic, problem)
        numbers = cd_fit_members.control_numbers(config, problem, *kept, stand_in=stand_in)
        ok, rows = compare.verdict(numbers, traffic["limits"])
        assert not ok, rows


@pytest.mark.parametrize("fault", faults.FAULTS + (faults.CARRIED_OVER,))
def test_planted_fault_is_not_correct(fault, capsys):
    # faults.planted tells a cd_fit driver by its module's name; this cell's
    # driver is a subclass of cd_fit's and takes the same faults
    with faults.planted(fault, cd_fit):
        result = _run(capsys)
    assert result["correct"] is False, result["compared"]
    if fault == faults.CARRIED_OVER:
        assert result["compared"]["repeat_gap"]["value"] > 1e-3


# -- the readers, on a hand-made context ------------------------------------
def span(name, start, end, **attrs):
    return {"name": name, "start": start, "end": end, "attrs": attrs, "depth": 1}


# window [100, 120]
HAND_MADE = [
    span("re/build_dataset", 10.0, 20.0, entities=1000, buckets=2,
         real_cells=6000, padded_cells=8000),
    span("re/build_dataset", 20.0, 22.0, entities=50, buckets=1,
         real_cells=1500, padded_cells=2000),
    span("re/solve_bucket", 60.0, 61.0, entities=900, state_bytes=10 ** 7),  # warm-up
    span("re/solve_bucket", 101.0, 102.0, entities=800, state_bytes=1596 * 800),
    span("re/solve_bucket", 102.0, 103.0, entities=200, state_bytes=1600 * 200),
    span("re/solve_bucket", 103.0, 103.5, entities=6),  # a one-shot bucket
]


def context(spans):
    return {"window": (100.0, 120.0), "steps": 2, "window_s": 20.0, "spans": spans,
            "counters": [], "shapes": {}, "peaks": None, "trace": None, "times": {}}


def test_the_readers_on_a_hand_made_window():
    c = context(HAND_MADE)
    assert READERS["re_state_bytes_per_entity"].read(c) == pytest.approx(
        (1596 * 800 + 1600 * 200) / 1000)
    assert READERS["re_fill_pct"].read(c) == pytest.approx(100.0 * 7500 / 10000)


def test_a_program_without_the_attrs_gives_none():
    """A program older than the attrs: the same spans, none of the new attrs."""
    bare = [span(s["name"], s["start"], s["end"], entities=s["attrs"]["entities"])
            for s in HAND_MADE]
    for name, reader in READERS.items():
        assert reader.read(context(bare)) is None, name
    for name, reader in READERS.items():
        assert reader.read(context([])) is None, name
