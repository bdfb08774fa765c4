"""Traffic ``refit``: one fixed-effect GLM, fitted whole again and again.

A step is one whole fit at the configured regularisation weight from the
zero model, through ``estimators/model_training.py`` ``train_glm`` as
``cli/train_glm`` calls it, ending in ``block_on_fit``. The device features
are built once in set-up (``GameData.sparse_features`` and
``LabeledData.create``, as the CLI builds them) and kept; every step is the
same work. The first fit is the warm-up step.
"""

from __future__ import annotations

import gc
import time
from typing import Dict

import numpy as np

from benchmarks import datagen
from benchmarks.traffic.steps import Window


make_problem = datagen.make_problem
STAND_INS = ("bfloat16", "half_batch", "unchanged")


class Driver:
    def __init__(self, config: dict, params: dict, seed: int, rehearsal: bool, log):
        self.config, self.params, self.seed = config, params, int(seed)
        self.rehearsal, self.log = rehearsal, log
        self.step_counters = []   # none: what a fit counted is on its glm/solve span
        self.times: Dict[str, float] = {}

    def prepare(self) -> None:
        import jax
        import jax.numpy as jnp

        from photon_ml_tpu.data.game_data import FeatureShard, GameData
        from photon_ml_tpu.opt.config import (
            GlmOptimizationConfiguration,
            OptimizerConfig,
            RegularizationContext,
        )
        from photon_ml_tpu.ops.data import LabeledData
        from photon_ml_tpu.types import RegularizationType, TaskType

        t0 = time.perf_counter()
        self.problem = datagen.make_problem(self.config, self.seed)
        self.log(f"data build {time.perf_counter() - t0:.1f}s (host, seeded)")
        rows = self.problem.train
        n, k = rows.cols.shape
        data = GameData(
            labels=rows.labels,
            feature_shards={"features": FeatureShard(
                np.repeat(np.arange(n, dtype=np.int64), k),
                rows.cols.reshape(-1), rows.vals.reshape(-1), self.problem.n_cols,
            )},
            id_tags={},
        )
        fe = self.config["fixed_effect"]
        t0 = time.perf_counter()
        features = data.sparse_features("features", engine=fe["sparse_engine"])
        jax.block_until_ready(features)
        self.times["feature_build_s"] = time.perf_counter() - t0
        self.log(f"feature build {self.times['feature_build_s']:.1f}s (routing or plan read, upload)")
        self.labeled = LabeledData.create(
            features, jnp.asarray(data.labels),
            offsets=jnp.asarray(data.offsets), weights=jnp.asarray(data.weights),
        )
        if fe["optimizer"] != "LBFGS" or fe["regularization"] != "ELASTIC_NET":
            raise ValueError(f"refit drives L-BFGS/OWL-QN with an elastic net, got {fe}")
        self.task = TaskType[self.config["task"]]
        self.configuration = GlmOptimizationConfiguration(
            optimizer_config=OptimizerConfig.lbfgs(
                max_iterations=int(fe["max_iterations"]),
                tolerance=float(fe["tolerance"]),
                history_length=int(fe["history_length"]),
                constraint_lower=fe.get("constraint_lower"),
                constraint_upper=fe.get("constraint_upper"),
            ),
            regularization=RegularizationContext(
                RegularizationType.ELASTIC_NET, alpha=float(fe["elastic_net_alpha"])
            ),
            regularization_weight=float(fe["regularization_weight"]),
        )
        self.fit = None

    def _step(self):
        from photon_ml_tpu.estimators.model_training import block_on_fit, train_glm

        t0 = time.perf_counter()
        fit = block_on_fit(train_glm(self.labeled, self.task, self.configuration)[0])
        return fit, time.perf_counter() - t0

    def run(self, window: Window) -> None:
        self.first_fit, _ = self._step()
        window.warmup_step_done()
        window.warmed_up()
        in_window = 0.0
        while True:
            self.fit, took = self._step()
            in_window += took
            if window.step_done():
                break
        self.times["solve_s_in_window"] = in_window

    def end_to_end(self, window: Window) -> dict:
        return window.train_step_s()

    def work_shapes(self) -> dict:
        c = self.config
        return {"nnz": int(c["n_rows"]) * int(c["nnz_per_row"]),
                "n_rows": int(c["n_rows"]), "n_cols": int(c["n_cols"])}

    def collect(self) -> None:
        self.produced = {
            "first": (np.asarray(self.first_fit.model.coefficients.means),
                      float(self.first_fit.result.value)),
            "last": (np.asarray(self.fit.model.coefficients.means),
                     float(self.fit.result.value)),
        }

    def release(self) -> None:
        import jax

        self.fit = self.first_fit = self.labeled = None
        gc.collect()
        jax.clear_caches()
        gc.collect()

    def check(self) -> Dict[str, float]:
        """The last step's fit (and the warm-up step's, which is the same
        work) against the reference's optimum: objective, coefficient norm,
        support, and the box and orthant conditions by the reference's own
        gradient."""
        from benchmarks.reference.glm import GlmReference

        ref = GlmReference(self.config, self.problem, "float32")
        self.kept_reference = (ref, ref.solve(log=self.log))  # for control_numbers (calibrate.py)
        return _numbers(*self.kept_reference, list(self.produced.values()))


def reference_run(config: dict, params: dict, problem, log=None):
    """(the float32 reference, its solve); the traffic's ``params`` hold
    nothing that it needs."""
    from benchmarks.reference.glm import GlmReference

    ref = GlmReference(config, problem, "float32")
    return ref, ref.solve(log=log)


def _numbers(ref, reference_fit, produced) -> Dict[str, float]:
    """``produced`` is [(coefficients, reported objective)]; the conditions
    are read on the last of them."""
    from benchmarks import compare

    w_ref, info = reference_fit
    w_ref = np.asarray(w_ref)
    nnz_ref = max(int(np.count_nonzero(w_ref)), 1)
    numbers = {"objective_gap": 0.0, "change_gap": 0.0, "support_gap": 0.0}
    for w, value in produced:
        numbers["objective_gap"] = max(
            numbers["objective_gap"], compare.relative_gap(value, info["value"]))
        numbers["change_gap"] = max(
            numbers["change_gap"], compare.worst_leaf_norm_gap({"fixed": w}, {"fixed": w_ref}))
        numbers["support_gap"] = max(
            numbers["support_gap"], abs(int(np.count_nonzero(w)) - nnz_ref) / nnz_ref)
    numbers["scored_objective_gap"] = compare.relative_gap(
        produced[-1][1], ref.objective(produced[-1][0]))
    numbers.update(ref.conditions(produced[-1][0]))
    return {k: compare._finite(v) for k, v in numbers.items()}


def control_numbers(config: dict, problem, reference, reference_fit, stand_in: str = "bfloat16",
                    log=None) -> Dict[str, float]:
    """A stand-in put in the program's place and compared as the program
    is; each has to come out as not correct. ``reference`` and
    ``reference_fit`` are the float32 reference and its solve (Driver.check
    keeps them).

    - "bfloat16": the control. The reference computed in bfloat16.
    - "half_batch": the float32 reference given the first half of the rows.
    - "unchanged": a fit that hands back the zero model it started from,
      reporting that model's objective.
    """
    from benchmarks.reference.glm import GlmReference

    if stand_in == "bfloat16":
        w, info = GlmReference(config, problem, "bfloat16").solve(log=log)
        produced = (np.asarray(w), info["value"])
    elif stand_in == "half_batch":
        halved = datagen.Problem(problem.n_cols, problem.train.first_half(),
                                 problem.held_out, problem.entity_counts)
        w, info = GlmReference(config, halved, "float32").solve(log=log)
        produced = (np.asarray(w), info["value"])
    elif stand_in == "unchanged":
        zero = np.zeros((problem.n_cols,), np.float32)
        produced = (zero, reference.objective(zero))
    else:
        raise ValueError(f"unknown stand-in {stand_in!r}")
    return _numbers(reference, reference_fit, [produced])
