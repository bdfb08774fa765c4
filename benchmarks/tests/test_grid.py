"""The cell ``glmix-1b-grid4.cd-fit-grid`` at the rehearsal size, on four
forced host devices: a sound run is ``correct``, reports the grid's layer
and holds no compile in its window; the control, every fault in the
reference and a sum over ``feat`` lost in the program itself are not; the
new readers give what a hand-made context holds and ``None`` where a program
makes no such span (the parent of PR 36); a tree without the names this cell
needs of the program exits at once.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_grid.py -q

A whole run needs four devices, which a process gets only before JAX starts:
those tests run ``run.py`` in a process of their own. The limits held here
are ``traffic/cd-fit-grid.tiny.json``'s; the cell's own were read on the chip
at the cell's size (PERF.md section 2).
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import compare, run as harness  # noqa: E402

CELL = "glmix-1b-grid4.cd-fit-grid.tiny"
READERS = {m.NAME: m for m in harness.list_layer_metrics()
           if m.NAME in ("collective_pct", "mesh_fetch_bytes.step", "grid_build_s")}


def _run_on_four_devices(code: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


ARGV = ["--workload", CELL, "--seed", "3600000011", "--seconds", "2"]


def test_sound_run_is_correct_and_reports_the_grid_layer():
    result = _run_on_four_devices(
        "import sys; from benchmarks import run; "
        f"sys.exit(run.main({ARGV + ['--trace', '1']!r}))")
    assert result["correct"] is True, result["compared"]
    assert result["rehearsal"] is True and result["device"]["count"] == 4
    assert result["compared"]["repeat_gap"]["value"] == 0.0
    metrics = result["metrics"]
    # a validation gathers the sharded models to score them on the host
    assert metrics["mesh_fetch_bytes.step"]["value"] > 0
    assert metrics["grid_build_s"]["value"] > 0
    assert metrics["grid_build_s"]["value"] <= metrics["routing_prep_s"]["value"]
    assert "collective_pct" not in metrics  # a device trace's: none on the CPU
    # after the warm-up fit nothing is traced, lowered or compiled again
    for name in ("retrace_s.step", "lower_s.step", "backend_compile_s.step",
                 "cache_misses.step", "fe_compile_s.step", "re_compile_s.step",
                 "cd_compile_s.step"):
        assert metrics[name]["value"] == 0.0, name
    assert result["attempted"] >= 2


def lose_the_sum_over_feat():
    """The fault ``one_feat_shard`` planted in the program: the grid's
    matvec hands back the margins of the first feat shard's columns alone,
    as a sum over ``feat`` that never happened would."""
    from unittest import mock

    from jax.sharding import PartitionSpec as P

    import jax
    from photon_ml_tpu.parallel import grid_features as G

    def matvec(self, w):
        def local_mv(shards, w_blk):
            tile = jax.tree.map(lambda a: a[0, 0], shards)
            return tile.matvec(w_blk[0])[None, None]

        out = G.shard_map(
            local_mv, mesh=self.mesh,
            in_specs=(P(G.DATA_AXIS, G.FEAT_AXIS), P(G.FEAT_AXIS)),
            out_specs=P(G.DATA_AXIS, G.FEAT_AXIS),
        )(self.shards, w.reshape(self._n_df(), -1))
        return out[:, 0].reshape(-1)

    return mock.patch.object(G.GridShardedFeatures, "matvec", matvec)


def test_a_sum_over_feat_lost_in_the_program_is_not_correct():
    result = _run_on_four_devices(
        "import sys; from benchmarks import run; from benchmarks.tests import test_grid\n"
        "with test_grid.lose_the_sum_over_feat():\n"
        f"    sys.exit(run.main({ARGV + ['--trace', '0']!r}))")
    assert result["correct"] is False, result["compared"]
    compared = result["compared"]
    assert compared["loss_gap"]["value"] > 10 * compared["loss_gap"]["limit"]
    assert compared["auc_gap"]["value"] > 10 * compared["auc_gap"]["limit"]


@pytest.mark.parametrize("stand_in", ["bfloat16", "half_batch", "unchanged", "carried_over",
                                      "one_feat_shard", "one_data_shard"])
def test_stand_in_is_not_correct(stand_in):
    """The control and each fault planted in the reference, put in the
    program's place, on three seeds; a lost sum fails the objective and the
    AUC by an order or more."""
    from benchmarks.traffic import cd_fit_grid

    assert stand_in in cd_fit_grid.STAND_INS and len(cd_fit_grid.STAND_INS) == 6
    w = harness.load_workload(CELL)
    config, traffic = w["config_doc"], w["traffic_doc"]
    for seed in (21, 22, 23):
        problem = cd_fit_grid.make_problem(config, seed)
        kept = cd_fit_grid.reference_run(config, traffic, problem)
        numbers = cd_fit_grid.control_numbers(config, problem, *kept, stand_in=stand_in)
        ok, rows = compare.verdict(numbers, traffic["limits"])
        assert not ok, rows
        if stand_in.startswith("one_"):
            limits = traffic["limits"]
            assert numbers["loss_gap"] > 10 * limits["loss_gap"], numbers
            assert numbers["auc_gap"] > 10 * limits["auc_gap"], numbers


def _span(name, start, end, **attrs):
    return {"name": name, "start": start, "end": end, "attrs": attrs, "depth": 1}


def _context(spans, self_times=None):
    return {"window": (10.0, 20.0), "steps": 2, "window_s": 10.0, "spans": spans,
            "counters": [], "shapes": {}, "times": {}, "peaks": None,
            "trace": None if self_times is None else {"self_times": self_times}}


GRID_SPANS = [
    _span("game/build_coordinate", 1.0, 6.0, kind="FixedEffectCoordinateConfiguration",
          mesh="2x2", devices=4),
    _span("grid/build_tile", 1.0, 4.0, dd=0, df=0, slots=1024, plan_cached=False),
    _span("grid/build_tile", 1.5, 5.0, dd=0, df=1, slots=1024, plan_cached=False),
    _span("cd/validate", 11.0, 11.5, coordinate="fixed", fetch_bytes=4096),
    _span("cd/validate", 16.0, 16.5, coordinate="fixed", fetch_bytes=4096),
    _span("cd/validate", 5.0, 5.5, coordinate="fixed", fetch_bytes=4096),  # the warm-up fit's
]
ONE_CHIP_SPANS = [
    _span("game/build_coordinate", 1.0, 6.0, kind="FixedEffectCoordinateConfiguration"),
    _span("cd/validate", 11.0, 11.5, coordinate="fixed"),
]
OPS = {"psum (all-reduce)": 1.0, "all-reduce-start": 0.25, "all-reduce-done": 0.25,
       "collective-permute": 0.5, "fusion": 5.0, "pallas:routed_map_kernel": 3.0}


def test_readers_read_what_a_grid_run_holds():
    context = _context(GRID_SPANS, OPS)
    assert READERS["collective_pct"].read(context) == pytest.approx(100 * 2.0 / 10.0)
    assert READERS["mesh_fetch_bytes.step"].read(context) == 4096.0
    assert READERS["grid_build_s"].read(context) == pytest.approx(4.0)  # a union
    assert READERS["collective_pct"].read(_context(GRID_SPANS)) is None  # no trace


def test_readers_return_nothing_where_the_program_has_no_grid():
    """One chip, or a tree before the spans said ``devices`` and counted
    fetches: no number, and no error."""
    context = _context(ONE_CHIP_SPANS, {"fusion": 5.0})
    for reader in READERS.values():
        assert reader.read(context) is None, reader.NAME
    assert len(READERS) == 3


def test_a_tree_without_the_grids_names_exits_at_once(monkeypatch):
    """``cd_fit_grid`` asks at import for what this cell needs of the
    program (PR 36's); a tree that lacks it leaves with run.py's
    EXIT_BAD_WORKLOAD before any work."""
    from photon_ml_tpu.parallel import grid_features

    monkeypatch.delattr(grid_features, "COLUMN_MULTIPLE")
    monkeypatch.delitem(sys.modules, "benchmarks.traffic.cd_fit_grid", raising=False)
    with pytest.raises(SystemExit) as leaving:
        importlib.import_module("benchmarks.traffic.cd_fit_grid")
    assert leaving.value.code == harness.EXIT_BAD_WORKLOAD
    sys.modules.pop("benchmarks.traffic.cd_fit_grid", None)


def test_the_cell_is_declared_as_the_harness_finds_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = json.load(f)
    (cell,) = [w for w in declared["workloads"] if w["name"] == "glmix-1b-grid4.cd-fit-grid"]
    assert cell["chips"] == 4 and cell["traffic"] == "cd-fit-grid"
    w = harness.load_workload(cell["name"])
    assert w["chips"] == 4 and w["config_doc"]["grid"]["n_data"] == 2
    (config,) = [c for c in declared["configs"] if c["name"] == cell["config"]]
    assert config["file"] == w["config_file"]
    assert sorted(config["reduced"]) == sorted(w["config_doc"]["reduced"])
    for name in READERS:
        (metric,) = [m for m in declared["per_layer"] if m["name"] == name]
        assert metric["workloads"] == [cell["name"]]
        assert metric["unit"] == READERS[name].UNIT and metric["source"] == READERS[name].SOURCE
