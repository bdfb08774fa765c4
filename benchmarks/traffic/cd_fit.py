"""Traffic ``cd-fit``: whole GLMix fits from the zero model, one after
another on one prepared data set.

A step is one whole fit: ``outer_iterations`` (the traffic file's; two)
outer CD iterations over the update order, each a fixed-effect solve, every
random-effect solve, the score-plane updates, the training objective and the
held-out AUC after each update, and the best-AUC model returned. The entry
is ``GameEstimator.fit_multiple(warm_start=False)``: the public path that
prepares the data once and fits one model a configuration, each from
nothing, as a sweep over one data set or a scheduled retrain does. Its
``configs`` argument is a sequence that this driver hands over lazily, one
empty override map a fit, until the window has closed; the estimator has no
other stop hook. Every fit is the same work. Data prep and the warm-up fit
with its compiles are set-up.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks import datagen
from benchmarks.traffic.steps import Window


class _Steps:
    """``configs`` for ``fit_multiple``: an empty override map a fit, for
    as long as the window lasts."""

    def __init__(self, window: Window, warmup_steps: int):
        self.window, self.warmup_steps = window, warmup_steps

    def __bool__(self) -> bool:
        return True

    def __len__(self) -> int:
        return self.warmup_steps + self.window.steps + 1

    def __iter__(self):
        for _ in range(self.warmup_steps):
            yield {}
            self.window.warmup_step_done()
        self.window.warmed_up()
        while True:
            yield {}
            if self.window.step_done():
                return


class _SolverStats:
    def __init__(self):
        self.events = []

    def on_event(self, event) -> None:
        from photon_ml_tpu.event import SolverStatsEvent

        if isinstance(event, SolverStatsEvent):
            self.events.append(event)

    def close(self) -> None:
        pass


make_problem = datagen.make_problem
STAND_INS = ("bfloat16", "half_batch", "unchanged", "carried_over")


class Driver:
    def __init__(self, config: dict, params: dict, seed: int, rehearsal: bool, log):
        self.config, self.params, self.seed = config, params, int(seed)
        self.rehearsal, self.log = rehearsal, log
        self.problem: Optional[datagen.Problem] = None
        self.outer_iterations = int(params["outer_iterations"])
        self.step_counters: List[dict] = []
        self.fits = None

    # -- set-up -----------------------------------------------------------
    def prepare(self) -> None:
        from photon_ml_tpu.data.game_data import FeatureShard, GameData

        t0 = time.perf_counter()
        self.problem = datagen.make_problem(self.config, self.seed)
        self.log(f"data build {time.perf_counter() - t0:.1f}s (host, seeded)")

        def game_data(rows: datagen.Rows) -> GameData:
            n, k = rows.cols.shape
            shards = {
                "global": FeatureShard(
                    np.repeat(np.arange(n, dtype=np.int64), k),
                    rows.cols.reshape(-1), rows.vals.reshape(-1),
                    self.problem.n_cols,
                )
            }
            for name, x in rows.entity_x.items():
                dim = x.shape[1]
                shards[name] = FeatureShard(
                    np.repeat(np.arange(n, dtype=np.int64), dim),
                    np.tile(np.arange(dim, dtype=np.int64), n),
                    x.reshape(-1), dim,
                )
            return GameData(
                labels=rows.labels, feature_shards=shards,
                id_tags={f"{name}Id": ids for name, ids in rows.entities.items()},
            )

        self.train = game_data(self.problem.train)
        self.held_out = game_data(self.problem.held_out)
        self.estimator = self._estimator()

    def _estimator(self):
        from photon_ml_tpu.data.random_effect import RandomEffectDataConfiguration
        from photon_ml_tpu.estimators.game import (
            FixedEffectCoordinateConfiguration,
            GameEstimator,
            RandomEffectCoordinateConfiguration,
        )
        from photon_ml_tpu.event import EventEmitter
        from photon_ml_tpu.opt.config import (
            GlmOptimizationConfiguration,
            OptimizerConfig,
            RegularizationContext,
        )
        from photon_ml_tpu.types import RegularizationType, TaskType

        def lbfgs_l2(c: dict):
            if c["optimizer"] != "LBFGS" or c["regularization"] != "L2":
                raise ValueError(f"cd-fit drives L-BFGS + L2 coordinates, got {c}")
            return GlmOptimizationConfiguration(
                optimizer_config=OptimizerConfig.lbfgs(
                    max_iterations=int(c["max_iterations"]),
                    tolerance=float(c["tolerance"]),
                    history_length=int(c["history_length"]),
                ),
                regularization=RegularizationContext(RegularizationType.L2),
                regularization_weight=float(c["regularization_weight"]),
            )

        class KeepsCoordinates(GameEstimator):
            """Keeps the coordinates it builds, so that the driver can read
            the random effects' bucket shapes (fit_multiple hands the
            coordinates to nobody, and a solver-stats event names its bucket
            by index)."""

            def __init__(self, *args, **kwargs):
                super().__init__(*args, **kwargs)
                self.built: Dict[str, object] = {}

            def _build_coordinate(self, cid, cfg, data):
                coord = super()._build_coordinate(cid, cfg, data)
                self.built[cid] = coord
                return coord

        fe = self.config["fixed_effect"]
        coordinates = {
            "fixed": FixedEffectCoordinateConfiguration(
                "global", lbfgs_l2(fe), sparse_engine=fe["sparse_engine"]
            )
        }
        for name, re in self.config["random_effects"].items():
            coordinates[name] = RandomEffectCoordinateConfiguration(
                name,
                RandomEffectDataConfiguration(
                    f"{name}Id",
                    active_data_upper_bound=re.get("active_cap"),
                    num_buckets=int(re.get("num_buckets", 1)),
                    seed=int(re.get("sample_seed", 0)),
                ),
                lbfgs_l2(re),
            )
        self.stats = _SolverStats()
        emitter = EventEmitter()
        emitter.register_listener(self.stats)
        self.emitter = emitter
        return KeepsCoordinates(
            task=TaskType[self.config["task"]],
            coordinates=coordinates,
            update_order=self.config["update_order"],
            num_outer_iterations=self.outer_iterations,
            emitter=emitter,
        )

    # -- warm-up and window ---------------------------------------------
    def run(self, window: Window) -> None:
        outer_on_step = window.on_step

        def on_step() -> None:
            self.step_counters.append(self._read_counters())
            if outer_on_step is not None:
                outer_on_step()

        window.on_step = on_step
        self.fits = self.estimator.fit_multiple(
            self.train, validation_data=self.held_out,
            configs=_Steps(window, int(self.params["warmup_steps"])), warm_start=False,
        )
        if self.emitter.listener_errors:
            raise RuntimeError("an event listener raised during the fit")

    def _read_counters(self) -> dict:
        """What the last fit's random effects counted: lane-iterations from
        the events the CD driver sent since the previous fit. (The fixed
        effect's counts are on the program's ``glm/solve`` spans.)"""
        built = self.estimator.built
        events, self.stats.events = self.stats.events, []
        lanes = []
        for e in events:
            bucket = built[e.coordinate_id].dataset.buckets[e.bucket]
            lanes.append({
                "coordinate": e.coordinate_id,
                "samples": int(bucket.X.shape[1]),
                "dim": int(bucket.X.shape[2]),
                "executed": int(e.executed_lane_iterations),
                "live": float(e.executed_lane_iterations) * (1.0 - float(e.wasted_lane_fraction)),
            })
        return {"re_lanes": lanes}

    def end_to_end(self, window: Window) -> dict:
        return window.train_step_s()

    def work_shapes(self) -> dict:
        c = self.config
        return {
            "nnz": int(c["n_rows"]) * int(c["nnz_per_row"]),
            "n_rows": int(c["n_rows"]),
            "n_cols": int(c["n_cols"]),
        }

    # -- what the timed path produced -------------------------------------
    def collect(self) -> None:
        """Host copies of what the check needs: every fit's readings, and
        the models the warm-up fit and the window's last fit returned; then
        the program's state can go."""
        fits = self.fits
        self.histories = [
            {
                "objective": [float(v) for _, v in f.objective_history],
                "validation": [float(v) for _, v in f.validation_history],
            }
            for f in fits
        ]
        self.models = {i: self._host_model(fits[i].model.models)
                       for i in sorted({0, len(fits) - 1})}

    def _host_model(self, models: dict) -> dict:
        out = {"fixed": np.asarray(models["fixed"].coefficients.means)}
        for name, re in self.config["random_effects"].items():
            m = models[name]
            table = np.zeros((re["n_entities"], re["dim"]), np.float32)
            for b, ids in enumerate(m.entity_ids):
                coef = np.asarray(m.coefficients[b])
                idx = np.asarray(m.proj_indices[b])
                valid = np.asarray(m.proj_valid[b])
                ent = np.asarray([int(i) for i in ids], dtype=np.int64)
                rows = np.broadcast_to(ent[:, None], idx.shape)
                table[rows[valid], idx[valid]] = coef[valid]
            out[name] = table
        return out

    def release(self) -> None:
        import jax

        self.fits = self.estimator = self.train = self.held_out = None
        self.emitter = self.stats = None
        gc.collect()
        jax.clear_caches()
        gc.collect()

    # -- correct ------------------------------------------------------------
    def check(self) -> Dict[str, float]:
        """The numbers compared (compare.py has the arithmetic): the warm-up
        fit and the window's last fit, which are the same work, against the
        plain reference's run of as many outer iterations from the zero
        model; their returned models scored by the reference; and every fit
        of the window against the warm-up fit."""
        # kept for control_numbers (calibrate.py)
        self.kept_reference = reference_run(self.config, self.params, self.problem, self.log)
        return _numbers(self.config, *self.kept_reference, self.histories, self.models, self.log)


def reference_run(config: dict, params: dict, problem, log=None):
    """(the float32 reference, its run of one fit: as many outer iterations
    as the traffic file gives a fit)."""
    from benchmarks.reference.glmix import GlmixReference

    ref = GlmixReference(config, problem, "float32")
    return ref, ref.run(int(params["outer_iterations"]), log=log)


def _numbers(config, ref, snaps, histories, models, log=None) -> Dict[str, float]:
    from benchmarks import compare

    complete_from = len(config["update_order"]) - 1
    numbers = compare.training_numbers(histories, models, snaps, complete_from, log)
    numbers.update(compare.scored_gaps(
        histories, models, complete_from, lambda m: _evaluate(ref, m)))
    numbers.update(compare.repeat_gap(histories))
    return numbers


def _evaluate(ref, model):
    return ref.evaluate(model["fixed"], {k: v for k, v in model.items() if k != "fixed"})


def control_numbers(config: dict, problem, reference, reference_snaps, stand_in: str = "bfloat16",
                    log=None) -> Dict[str, float]:
    """A stand-in put in the program's place and compared as the program
    is: its run is the warm-up fit and the window's last fit alike, but for
    ``carried_over``. Each has to come out as not correct. ``reference`` and
    ``reference_snaps`` are the float32 reference and its run (Driver.check
    keeps them).

    - "bfloat16": the control. The reference computed in bfloat16.
    - "half_batch": the float32 reference given the first half of the
      training rows (half of the batch left out).
    - "unchanged": the float32 reference whose second outer iteration
      returns its state unchanged: its updates report the first iteration's
      last objective and AUC and hand back its model.
    - "carried_over": the warm-up fit is the float32 reference's; the last
      fit is the reference started from the model that fit returned, as
      ``fit_multiple(warm_start=True)`` would start it.
    """
    from benchmarks import compare
    from benchmarks.reference.glmix import GlmixReference, Snapshot

    per = len(config["update_order"])
    outer = len(reference_snaps) // per
    runs = None
    if stand_in == "bfloat16":
        low = GlmixReference(config, problem, "bfloat16").run(outer, log=log)
    elif stand_in == "half_batch":
        halved = datagen.Problem(problem.n_cols, problem.train.first_half(),
                                 problem.held_out, problem.entity_counts)
        low = GlmixReference(config, halved, "float32").run(outer, log=log)
    elif stand_in == "unchanged":
        stuck = reference_snaps[per - 1]
        low = list(reference_snaps[:per]) + [
            Snapshot(s.step, s.coordinate, stuck.objective, stuck.auc, stuck.fixed, stuck.random)
            for s in reference_snaps[per:]
        ]
    elif stand_in == "carried_over":
        first = list(reference_snaps)
        returned = first[compare.picked_update([s.auc for s in first], per - 1)]
        runs = [first, reference.run(outer, log=log, start=returned)]
    else:
        raise ValueError(f"unknown stand-in {stand_in!r}")
    histories, models = [], {}
    for i, mine in enumerate(runs or [low, low]):
        hist = {"objective": [m.objective for m in mine], "validation": [m.auc for m in mine]}
        histories.append(hist)
        picked = mine[compare.picked_update(hist["validation"], per - 1)]
        models[i] = {"fixed": np.asarray(picked.fixed),
                     **{k: np.asarray(v) for k, v in picked.random.items()}}
    return _numbers(config, reference, reference_snaps, histories, models)
