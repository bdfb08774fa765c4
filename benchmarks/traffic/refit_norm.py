"""Traffic ``refit-norm``: one fixed-effect GLM on normalized features,
fitted whole again and again.

``refit``'s step (one whole ``train_glm`` from the zero model at the
configured weight, ending in ``block_on_fit``), with the set-up
``cli/train_glm.py`` makes when a normalization is asked for: the device
features, ``stat.summarize`` over them, ``build_normalization_context``, and
``LabeledData.create(..., norm=...)``. Features, factor and labels are kept
on the device; the mapping of the optimum back to the original feature space
is inside ``train_glm`` and so inside every step. Drives TRON with an L2
penalty (``refit`` drives L-BFGS / OWL-QN with an elastic net).
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from benchmarks import datagen, datagen_linear
from benchmarks.traffic import refit

make_problem = datagen_linear.make_problem
STAND_INS = ("bfloat16", "half_batch", "unchanged", "unnormalized")


class Driver(refit.Driver):
    def prepare(self) -> None:
        import jax
        import jax.numpy as jnp

        from photon_ml_tpu.data.game_data import FeatureShard, GameData
        from photon_ml_tpu.normalization import build_normalization_context
        from photon_ml_tpu.opt.config import (
            GlmOptimizationConfiguration,
            OptimizerConfig,
            RegularizationContext,
        )
        from photon_ml_tpu.ops.data import LabeledData
        from photon_ml_tpu.stat.summary import summarize
        from photon_ml_tpu.types import NormalizationType, RegularizationType, TaskType

        fe = self.config["fixed_effect"]
        if fe["optimizer"] != "TRON" or fe["regularization"] != "L2" or fe["intercept"]:
            raise ValueError(f"refit-norm drives TRON with L2 and no intercept, got {fe}")
        t0 = time.perf_counter()
        self.problem = make_problem(self.config, self.seed)
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
        t0 = time.perf_counter()
        features = data.sparse_features("features", engine=fe["sparse_engine"])
        jax.block_until_ready(features)
        self.times["feature_build_s"] = time.perf_counter() - t0
        self.log(f"feature build {self.times['feature_build_s']:.1f}s (routing or plan read, upload)")

        plain = LabeledData.create(
            features, jnp.asarray(data.labels), offsets=jnp.asarray(data.offsets),
            weights=jnp.asarray(data.weights),
        )
        t0 = time.perf_counter()
        summary = summarize(plain)
        norm = build_normalization_context(
            NormalizationType[fe["normalization"]], mean=summary.mean,
            variance=summary.variance, max_magnitude=summary.max_abs, intercept_index=None,
        )
        jax.block_until_ready(norm)
        self.times["summarize_s"] = time.perf_counter() - t0
        self.log(f"summarize and normalization context {self.times['summarize_s']:.1f}s")
        del summary  # eight [n_cols] vectors; the factor is what a fit needs
        self.labeled = plain.replace(norm=norm)
        self.task = TaskType[self.config["task"]]
        self.configuration = GlmOptimizationConfiguration(
            optimizer_config=OptimizerConfig.tron(
                max_iterations=int(fe["max_iterations"]),
                tolerance=float(fe["tolerance"]),
                max_cg_iterations=int(fe["max_cg_iterations"]),
            ),
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=float(fe["regularization_weight"]),
        )
        self.fit = None

    def collect(self) -> None:
        super().collect()
        self.produced_factor = np.asarray(self.labeled.norm.factor)

    def check(self) -> Dict[str, float]:
        """The warm-up fit and the window's last fit (the same work) against
        the reference's optimum in the original space, the program's factor
        against the reference's own statistics."""
        self.kept_reference = reference_run(self.config, self.params, self.problem, self.log)
        produced = [(w, value, self.produced_factor) for w, value in self.produced.values()]
        return _numbers(*self.kept_reference, produced)


def reference_run(config: dict, params: dict, problem, log=None):
    """(the float32 reference, its solve); the traffic's ``params`` hold
    nothing that it needs."""
    from benchmarks.reference.linear import LinearReference

    ref = LinearReference(config, problem, "float32")
    return ref, ref.solve(log=log)


def _numbers(ref, reference_fit, produced) -> Dict[str, float]:
    """``produced`` is [(original-space coefficients, reported objective,
    factor)]; the gradient is read at the last of them."""
    from benchmarks import compare

    w_ref, info = reference_fit
    w_ref, factor_ref = np.asarray(w_ref), np.asarray(ref.factor, np.float64)
    seen = ref.statistics["nonzeros"] > 0
    numbers = {"objective_gap": 0.0, "change_gap": 0.0, "factor_gap": 0.0}
    for w, value, factor in produced:
        numbers["objective_gap"] = max(
            numbers["objective_gap"], compare.relative_gap(value, info["value"]))
        numbers["change_gap"] = max(
            numbers["change_gap"], compare.worst_leaf_norm_gap({"fixed": w}, {"fixed": w_ref}))
        gaps = np.abs(np.asarray(factor, np.float64)[seen] - factor_ref[seen]) / factor_ref[seen]
        numbers["factor_gap"] = max(numbers["factor_gap"], float(gaps.max()))
    w, value, _ = produced[-1]
    numbers["scored_objective_gap"] = compare.relative_gap(value, ref.objective(w))
    numbers["stationarity"] = ref.stationarity(w)
    return {k: compare._finite(v) for k, v in numbers.items()}


def control_numbers(config: dict, problem, reference, reference_fit, stand_in: str = "bfloat16",
                    log=None) -> Dict[str, float]:
    """A stand-in put in the program's place and compared as the program
    is; each has to come out as not correct. ``reference`` and
    ``reference_fit`` are the float32 reference and its solve (Driver.check
    keeps them).

    - "bfloat16": the control. The reference, statistics included, computed
      in bfloat16.
    - "half_batch": the float32 reference given the first half of the rows.
    - "unchanged": a fit that hands back the zero model it started from,
      reporting that model's objective, beside a sound factor.
    - "unnormalized": the float32 reference solved with factor 1: the L2
      penalty on the original coefficients, whatever the columns' scales.
    """
    from benchmarks.reference.linear import LinearReference

    def solved(ref):
        w, info = ref.solve(log=log)
        return np.asarray(w), info["value"], np.asarray(ref.factor)

    if stand_in == "bfloat16":
        produced = solved(LinearReference(config, problem, "bfloat16"))
    elif stand_in == "half_batch":
        halved = datagen.Problem(problem.n_cols, problem.train.first_half(),
                                 problem.held_out, problem.entity_counts)
        produced = solved(LinearReference(config, halved, "float32"))
    elif stand_in == "unnormalized":
        produced = solved(LinearReference(config, problem, "float32", normalization="NONE"))
    elif stand_in == "unchanged":
        zero = np.zeros((problem.n_cols,), np.float32)
        produced = (zero, reference.objective(zero), np.asarray(reference.factor))
    else:
        raise ValueError(f"unknown stand-in {stand_in!r}")
    return _numbers(reference, reference_fit, [produced])
