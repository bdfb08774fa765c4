"""Traffic ``cd-fit-ratings``: whole fits of the full GAME ratings model
from the zero model, one after another on one prepared data set.

``cd_fit``'s step and entry (one whole ``GameEstimator.fit_multiple(
warm_start=False)`` fit a step, the data prepared once, the warm-up fit with
its compiles in set-up), with what a ratings model adds: the squared loss, a
fourth coordinate of type ``factored_random`` over the users whose feature
shard is the row's item id one-hot (the user x item factorization), and the
held-out RMSE, lower is better, choosing the model a fit returns. A fit is
``outer_iterations`` (the traffic file's; two) outer CD iterations over
``fixed, per_user, per_item, user_item_mf``: eight block updates, two
alternations inside each factored update.

The comparison that decides ``correct`` is this module's own (``compare.py``
reads AUC and picks a maximum); ``compare``'s leaf, relative-gap and repeat
arithmetic is used as it is.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Sequence

import numpy as np

from benchmarks import compare, datagen, datagen_ratings
from benchmarks.traffic import cd_fit

make_problem = datagen_ratings.make_problem
STAND_INS = ("bfloat16", "half_batch", "unchanged", "carried_over", "frozen_matrix")


# the factored coordinate: its block in the configuration file and its name
# in the update order
MF = "user_item_mf"


class Driver(cd_fit.Driver):
    # -- set-up -----------------------------------------------------------
    def prepare(self) -> None:
        from photon_ml_tpu.data.game_data import FeatureShard, GameData

        t0 = time.perf_counter()
        self.problem = make_problem(self.config, self.seed)
        self.log(f"data build {time.perf_counter() - t0:.1f}s (host, seeded)")
        mf = self.config[MF]
        n_items = int(self.config["random_effects"][mf["items"]]["n_entities"])

        def game_data(rows: datagen.Rows) -> GameData:
            n, k = rows.cols.shape
            every_row = np.arange(n, dtype=np.int64)
            shards = {
                "global": FeatureShard(
                    np.repeat(every_row, k), rows.cols.reshape(-1), rows.vals.reshape(-1),
                    self.problem.n_cols,
                ),
                # the factored coordinate's features: the row's item, one-hot
                "item_id": FeatureShard(
                    every_row, rows.entities[mf["items"]], np.ones(n, np.float32), n_items,
                ),
            }
            for name, x in rows.entity_x.items():
                dim = x.shape[1]
                shards[name] = FeatureShard(
                    np.repeat(every_row, dim), np.tile(np.arange(dim, dtype=np.int64), n),
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
        """``cd_fit``'s estimator (the fixed effect and the random effects
        from the configuration, the squared loss and so the RMSE evaluator
        from its task) with the factored coordinate added."""
        from photon_ml_tpu.algorithm.factored_random_effect import MFOptimizationConfiguration
        from photon_ml_tpu.data.random_effect import RandomEffectDataConfiguration
        from photon_ml_tpu.estimators.game import FactoredRandomEffectCoordinateConfiguration
        from photon_ml_tpu.opt.config import (
            GlmOptimizationConfiguration,
            OptimizerConfig,
            RegularizationContext,
        )
        from photon_ml_tpu.types import RegularizationType

        def lbfgs_l2(c: dict):
            if c["optimizer"] != "LBFGS" or c["regularization"] != "L2":
                raise ValueError(f"cd-fit-ratings drives L-BFGS + L2 coordinates, got {c}")
            return GlmOptimizationConfiguration(
                optimizer_config=OptimizerConfig.lbfgs(
                    max_iterations=int(c["max_iterations"]),
                    tolerance=float(c["tolerance"]),
                    history_length=int(c["history_length"]),
                ),
                regularization=RegularizationContext(RegularizationType.L2),
                regularization_weight=float(c["regularization_weight"]),
            )

        estimator = super()._estimator()
        if estimator.evaluator.name != self.config["evaluator"] or estimator.evaluator.larger_is_better:
            raise ValueError(f"the configuration states {self.config['evaluator']}, lower is "
                             f"better; the estimator chose {estimator.evaluator.name}")
        mf = self.config[MF]
        estimator.coordinate_configs[MF] = (
            FactoredRandomEffectCoordinateConfiguration(
                "item_id",
                RandomEffectDataConfiguration(
                    f"{mf['entities']}Id", num_buckets=int(mf["num_buckets"])),
                MFOptimizationConfiguration(
                    num_latent_factors=int(mf["latent_factors"]),
                    num_iterations=int(mf["alternations"]),
                    seed=self.seed,
                ),
                optimizer=lbfgs_l2(mf["latent"]),
                matrix_optimizer=lbfgs_l2(mf["matrix"]),
            )
        )
        return estimator

    # -- counters and shapes ------------------------------------------------
    def _read_counters(self) -> dict:
        """``cd_fit``'s lane counters; a latent solve's lanes work in the
        ``latent_factors`` dimensions of the projected blocks, not in the
        local dimension of the coordinate's own."""
        counters = super()._read_counters()
        for lane in counters["re_lanes"]:
            if lane["coordinate"] == MF:
                lane["dim"] = int(self.config[MF]["latent_factors"])
        return counters

    def work_shapes(self) -> dict:
        shapes = super().work_shapes()
        coordinate = self.estimator.built.get(MF)
        if coordinate is not None:
            shapes["mf_latent_factors"] = int(self.config[MF]["latent_factors"])
            shapes["mf_buckets"] = [
                {"entities": int(b.X.shape[0]), "samples": int(b.X.shape[1]),
                 "dim": int(b.X.shape[2])}
                for b in coordinate.dataset.buckets
            ]
        return shapes

    # -- what the timed path produced -------------------------------------
    def _host_model(self, models: dict) -> dict:
        out = super()._host_model(models)
        m = models[MF]
        mf = self.config[MF]
        n_users = int(self.config["random_effects"][mf["entities"]]["n_entities"])
        latent = np.zeros((n_users, int(mf["latent_factors"])), np.float32)
        for b, ids in enumerate(m.latent.entity_ids):
            latent[np.asarray([int(i) for i in ids], dtype=np.int64)] = np.asarray(
                m.latent.coefficients[b])
        out["latent"], out["matrix"] = latent, np.asarray(m.projection_matrix)
        return out

    # -- correct ------------------------------------------------------------
    def check(self) -> Dict[str, float]:
        """The numbers compared: the warm-up fit and the window's last fit,
        which are the same work, against the plain reference's run of as
        many outer iterations from the zero model; their returned models
        scored by the reference; and every fit of the window against the
        warm-up fit."""
        # kept for control_numbers (calibrate.py)
        self.kept_reference = reference_run(self.config, self.params, self.problem, self.log)
        return _numbers(self.config, *self.kept_reference, self.histories, self.models, self.log)


def reference_run(config: dict, params: dict, problem, log=None):
    """(the float32 reference, its run of one fit: as many outer iterations
    as the traffic file gives a fit)."""
    from benchmarks.reference.game_mf import GameMfReference

    ref = GameMfReference(config, problem, problem.seed, "float32")
    return ref, ref.run(int(params["outer_iterations"]), log=log)


def picked_update(validation: Sequence[float], complete_from: int) -> int:
    """Which update's model a fit returns, by the rule the configuration
    states: the held-out RMSE's first minimum over the updates at which
    every coordinate has a model (``compare.picked_update`` with the order
    turned round)."""
    return compare.picked_update([-v for v in validation], complete_from)


def training_numbers(histories: List[dict], models: Dict[int, dict], reference: list,
                     complete_from: int, log=None) -> Dict[str, float]:
    """``compare.training_numbers`` for a lower-is-better held-out metric and
    a model with factored leaves. loss_gap: worst relative gap of the
    objective after every update. rmse_gap: worst absolute gap of the
    held-out RMSE there. change_gap: worst leaf (fixed, the random effects,
    ``latent``, ``matrix``) of the gap of norms of the model the fit
    returned."""
    loss_gap = rmse_gap = change_gap = 0.0
    for fit, model in models.items():
        hist = histories[fit]
        if len(hist["objective"]) != len(reference) or len(hist["validation"]) != len(reference):
            return {"loss_gap": math.inf, "rmse_gap": math.inf, "change_gap": math.inf}
        losses = [compare.relative_gap(p, s.objective) for p, s in zip(hist["objective"], reference)]
        rmses = [abs(p - s.rmse) for p, s in zip(hist["validation"], reference)]
        loss_gap, rmse_gap = max(loss_gap, *losses), max(rmse_gap, *rmses)
        if log is not None:
            log(f"fit {fit} by update: objective gap " + " ".join(f"{g:.3g}" for g in losses)
                + " ; rmse gap " + " ".join(f"{g:.3g}" for g in rmses))
        snap = reference[picked_update(hist["validation"], complete_from)]
        change_gap = max(change_gap, compare.worst_leaf_norm_gap(
            model, snap.leaves(), log and (lambda line, fit=fit: log(f"fit {fit} {line}"))))
    return {"loss_gap": compare._finite(loss_gap), "rmse_gap": compare._finite(rmse_gap),
            "change_gap": compare._finite(change_gap)}


def scored_gaps(histories: List[dict], models: Dict[int, dict], complete_from: int,
                reference) -> Dict[str, float]:
    """Every kept model that a fit returned, scored by the reference: the
    objective and the held-out RMSE the program reported for the update it
    returned against the reference scorer's of that same model. They read the
    arithmetic of the timed path's maps, score plane, loss and evaluator,
    free of where the solvers stopped."""
    objective = rmse = 0.0
    for fit, model in models.items():
        hist = histories[fit]
        i = picked_update(hist["validation"], complete_from)
        scored = reference.evaluate(model)
        objective = max(objective, compare.relative_gap(hist["objective"][i], scored.objective))
        rmse = max(rmse, abs(hist["validation"][i] - scored.rmse))
    return {"scored_objective_gap": compare._finite(objective),
            "scored_rmse_gap": compare._finite(rmse)}


def _numbers(config, ref, snaps, histories, models, log=None) -> Dict[str, float]:
    complete_from = len(config["update_order"]) - 1
    numbers = training_numbers(histories, models, snaps, complete_from, log)
    numbers.update(scored_gaps(histories, models, complete_from, ref))
    numbers.update(compare.repeat_gap(histories))
    return numbers


def control_numbers(config: dict, problem, reference, reference_snaps, stand_in: str = "bfloat16",
                    log=None) -> Dict[str, float]:
    """A stand-in put in the program's place and compared as the program
    is: its run is the warm-up fit and the window's last fit alike, but for
    ``carried_over``. Each has to come out as not correct. ``reference`` and
    ``reference_snaps`` are the float32 reference and its run (Driver.check
    keeps them).

    - "bfloat16": the control. The reference computed in bfloat16.
    - "half_batch": the float32 reference given the first half of the
      training rows.
    - "unchanged": the float32 reference whose second outer iteration
      returns its state unchanged.
    - "carried_over": the warm-up fit is the float32 reference's; the last
      fit is the reference started from the model that fit returned.
    - "frozen_matrix": the float32 reference with step (b) of the factored
      coordinate left out: the projection matrix stays where it started.
    """
    from benchmarks.reference.game_mf import GameMfReference

    per = len(config["update_order"])
    outer = len(reference_snaps) // per
    runs = None
    if stand_in == "bfloat16":
        low = GameMfReference(config, problem, problem.seed, "bfloat16").run(outer, log=log)
    elif stand_in == "half_batch":
        halved = dataclasses.replace(problem, train=problem.train.first_half())
        low = GameMfReference(config, halved, problem.seed, "float32").run(outer, log=log)
    elif stand_in == "unchanged":
        stuck = reference_snaps[per - 1]
        low = list(reference_snaps[:per]) + [
            dataclasses.replace(stuck, step=s.step, coordinate=s.coordinate)
            for s in reference_snaps[per:]
        ]
    elif stand_in == "carried_over":
        first = list(reference_snaps)
        returned = first[picked_update([s.rmse for s in first], per - 1)]
        runs = [first, reference.run(outer, log=log, start=returned)]
    elif stand_in == "frozen_matrix":
        low = reference.run(outer, log=log, frozen_matrix=True)
    else:
        raise ValueError(f"unknown stand-in {stand_in!r}")
    histories, models = [], {}
    for i, mine in enumerate(runs or [low, low]):
        hist = {"objective": [m.objective for m in mine], "validation": [m.rmse for m in mine]}
        histories.append(hist)
        models[i] = mine[picked_update(hist["validation"], per - 1)].leaves()
    return _numbers(config, reference, reference_snaps, histories, models)
