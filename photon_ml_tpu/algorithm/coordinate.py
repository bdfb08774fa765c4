"""Coordinates: the per-block training units of GAME coordinate descent.

Reference parity: algorithm/Coordinate.scala:27 (updateModel with residual
offsets :59-62 — ``dataSet.addScoresToOffsets(score)`` then optimize the
coordinate alone), FixedEffectCoordinate.scala:34 (whole-data GLM solve;
score :159-166) and RandomEffectCoordinate.scala:39 (per-entity local solves;
active+passive scoring :157-187).

A coordinate owns its (device-resident) dataset and knows how to (a) train
its model given residual offsets from all other coordinates, and (b) produce
raw per-row scores aligned with the global row order.
"""

from __future__ import annotations

import abc
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.parallel.mesh import fetch_global, mesh_attrs

from photon_ml_tpu.data.random_effect import RandomEffectDataset
from photon_ml_tpu.estimators.model_training import train_glm
from photon_ml_tpu.estimators.random_effect import (
    score_random_effects,
    score_random_effects_device,
    train_random_effects,
)
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.models.random_effect import RandomEffectModel
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration
from photon_ml_tpu.opt.tracking import (
    FixedEffectOptimizationTracker,
    OptimizationStatesTracker,
    RandomEffectOptimizationTracker,
)
from photon_ml_tpu.sampler import down_sampler_for
from photon_ml_tpu.telemetry import note_jit_trace, span
from photon_ml_tpu.types import TaskType


class Coordinate(abc.ABC):
    """One block of the GAME model (reference Coordinate.scala:27)."""

    # True when score_device/update_model_device avoid ALL row-length
    # host<->device transfers (overridden by the concrete coordinates that
    # implement a real device path); the CD driver falls back through the
    # host methods — and counts the transfers — when False.
    supports_device_plane = False

    @abc.abstractmethod
    def update_model(self, model, residual_scores: np.ndarray):
        """Train this coordinate against residual scores from the others
        (the offsets trick, Coordinate.scala:59-62). model may be None
        (first pass) or the previous model (warm start)."""

    @abc.abstractmethod
    def score(self, model) -> np.ndarray:
        """Raw scores x.w per row of THIS coordinate's training data,
        aligned to global row order, zeros for rows it does not cover."""

    def update_model_device(self, model, residual_scores: jax.Array):
        """``update_model`` with a device-resident residual plane. The base
        implementation round-trips through host (a coordinate without a
        device path of its own); the fixed-effect, random-effect and
        factored coordinates override it with zero-row-transfer versions."""
        return self.update_model(model, np.asarray(residual_scores))

    def score_device(self, model) -> jax.Array:
        """``score`` as a device-resident [num_rows] array. Base
        implementation uploads the host scores; overridden with direct
        device programs where the coordinate's data is device-resident."""
        return jnp.asarray(self.score(model))


@jax.jit
def _fused_residual_offsets(base: jax.Array, residual: jax.Array) -> jax.Array:
    """base_offsets + residual in one program, zero-padding the residual up
    to the (device-grid) padded batch length when needed. Shapes are static
    at trace time, so the pad + add fuse into a single XLA computation."""
    if residual.shape[0] < base.shape[0]:
        residual = jnp.pad(residual, (0, base.shape[0] - residual.shape[0]))
    return base + residual


@jax.jit
def _fe_score(features, w: jax.Array) -> jax.Array:
    """``features.matvec(w)`` as one program, the same function object for
    the life of the process: ``jax.jit`` keys its caches on the function, and
    a routed engine's eager matvec makes a fresh Pallas wrapper per kernel
    per call, which never hits them. The features are an argument (a pytree:
    plans and values as leaves, sizes static), so every engine, coordinate
    object and ``fit_multiple`` configuration of one tree structure and
    shapes dispatches what the first call compiled."""
    note_jit_trace("fe_score")  # fires only on a (re)trace
    return features.matvec(w)


@dataclasses.dataclass
class FixedEffectCoordinate(Coordinate):
    """Global GLM over one feature shard (reference
    FixedEffectCoordinate.scala:34). ``data`` carries the GAME-level base
    offsets; residual scores are added on top per update."""

    data: LabeledData
    task: TaskType
    configuration: GlmOptimizationConfiguration
    down_sampling_seed: int = 0
    # when data.norm is set, the shift modes need the intercept slot to map
    # coefficients back to the original space (train_glm contract)
    intercept_index: Optional[int] = None
    # attach per-coefficient variances ~ 1/(H_jj+eps) to trained models
    # (reference COMPUTE_VARIANCE -> DistributedOptimizationProblem.scala:80-94)
    compute_variances: bool = False
    # telemetry from the most recent update (reference
    # FixedEffectOptimizationTracker.scala)
    last_tracker: Optional[FixedEffectOptimizationTracker] = dataclasses.field(
        default=None, repr=False
    )
    # multi-chip layouts pad the batch and the feature axis to the device
    # grid; the coordinate speaks global (unpadded) shapes at its boundary
    # (models carry [num_real_cols] coefficients, scores are [num_real_rows])
    num_real_rows: Optional[int] = None
    num_real_cols: Optional[int] = None
    # (model, padded solve vector) for the model last returned by
    # update_model, the vector kept with the sharding the jit'd solve
    # produced (feat-sharded on a grid): warm starts and scoring reuse it
    # instead of re-materializing the full [d_pad] vector on one device
    # each outer iteration. The strong model reference keys the cache by
    # identity safely (no id() reuse after garbage collection).
    _w_padded_cache: Optional[tuple] = dataclasses.field(
        default=None, repr=False
    )

    supports_device_plane = True

    def update_model(
        self, model: Optional[GeneralizedLinearModel], residual_scores: np.ndarray
    ) -> GeneralizedLinearModel:
        residual = np.asarray(residual_scores)
        n_pad = self.data.num_rows
        if residual.shape[0] < n_pad:
            residual = np.pad(residual, (0, n_pad - residual.shape[0]))
        return self._update_with_offsets(
            model, self.data.offsets + jnp.asarray(residual)
        )

    def update_model_device(
        self, model: Optional[GeneralizedLinearModel], residual_scores: jax.Array
    ) -> GeneralizedLinearModel:
        """Device-plane update: the residual stays on device and the pad +
        base-offset add run as ONE fused jit program feeding the solve — no
        row-length host transfer anywhere on this path."""
        return self._update_with_offsets(
            model, _fused_residual_offsets(self.data.offsets, residual_scores)
        )

    def _update_with_offsets(
        self, model: Optional[GeneralizedLinearModel], offsets: jax.Array
    ) -> GeneralizedLinearModel:
        with span(
            "fe/solve",
            device_sync=True,
            optimizer=self.configuration.optimizer_config.optimizer.name,
        ):
            return self._solve_with_offsets(model, offsets)

    def _solve_with_offsets(
        self, model: Optional[GeneralizedLinearModel], offsets: jax.Array
    ) -> GeneralizedLinearModel:
        data = self.data.replace(offsets=offsets)
        rate = self.configuration.down_sampling_rate
        if rate < 1.0:
            # runWithSampling (reference DistributedOptimizationProblem
            # :143-155): down-sample before the solve, weights re-scaled so
            # the objective stays unbiased.
            sampler = down_sampler_for(self.task, rate)
            weights = sampler.sample_weights(
                fetch_global(data.labels), fetch_global(data.weights),
                seed=self.down_sampling_seed,
            )
            data = data.replace(weights=jnp.asarray(weights))
        fit = train_glm(
            data,
            self.task,
            self.configuration,
            initial_model=self._pad_model(model),
            compute_variances=self.compute_variances,
            intercept_index=self.intercept_index,
        )[0]
        self.last_tracker = FixedEffectOptimizationTracker(
            states=OptimizationStatesTracker.from_result(fit.result)
        )
        trimmed = self._trim_model(fit.model)
        if self.num_real_cols is not None:
            # fit.model's means come straight out of the jit'd solve with
            # whatever sharding GSPMD chose (feat-sharded on a grid)
            self._w_padded_cache = (trimmed, fit.model.coefficients.means)
        return trimmed

    def _cached_padded_w(self, model) -> Optional[jax.Array]:
        if self._w_padded_cache is not None and self._w_padded_cache[0] is model:
            return self._w_padded_cache[1]
        return None

    def _pad_model(
        self, model: Optional[GeneralizedLinearModel]
    ) -> Optional[GeneralizedLinearModel]:
        """Warm starts arrive in real [d]; the padded layout trains in
        [d_pad] (trailing zeros for the dead columns). The padded vector of
        the model this coordinate itself produced is served from the
        sharded cache."""
        if model is None or self.num_real_cols is None:
            return model
        return model.replace(
            coefficients=model.coefficients.replace(
                means=self._padded_w(model), variances=None
            )
        )

    def _trim_model(self, model: GeneralizedLinearModel) -> GeneralizedLinearModel:
        if self.num_real_cols is None:
            return model
        d = self.num_real_cols
        coef = model.coefficients
        if coef.means.shape[0] == d:
            return model
        return model.replace(
            coefficients=coef.replace(
                means=coef.means[:d],
                variances=None if coef.variances is None else coef.variances[:d],
            )
        )

    def _padded_w(self, model: GeneralizedLinearModel) -> jax.Array:
        """The [d_pad] solve-space weight vector for ``model``, cached by
        model identity: a miss pads once and REFILLS the cache, so repeated
        score calls against the same trimmed model (every CD residual uses
        the other coordinates' scores) never re-pad."""
        w = self._cached_padded_w(model)
        if w is None:
            w = jnp.asarray(model.coefficients.means)
            if self.num_real_cols is not None and w.shape[0] < self.data.dim:
                w = jnp.pad(w, (0, self.data.dim - w.shape[0]))
            self._w_padded_cache = (model, w)
        return w

    def score(self, model: GeneralizedLinearModel) -> np.ndarray:
        scores = fetch_global(_fe_score(self.data.features, self._padded_w(model)))
        if self.num_real_rows is not None:
            scores = scores[: self.num_real_rows]
        return scores

    def score_device(self, model: GeneralizedLinearModel) -> jax.Array:
        """Device-plane ``score``: the matvec result never leaves the mesh;
        padded batch rows are sliced off on device."""
        scores = _fe_score(self.data.features, self._padded_w(model))
        if self.num_real_rows is not None:
            scores = scores[: self.num_real_rows]
        return scores


@dataclasses.dataclass
class RandomEffectCoordinate(Coordinate):
    """Per-entity GLMs over one feature shard (reference
    RandomEffectCoordinate.scala:39). Residual offsets are re-grouped into
    the entity blocks on each update."""

    dataset: RandomEffectDataset
    task: TaskType
    configuration: GlmOptimizationConfiguration
    base_offsets: np.ndarray  # GAME-level offsets, original row order
    # telemetry from the most recent update (reference
    # RandomEffectOptimizationTracker.scala)
    last_tracker: Optional[RandomEffectOptimizationTracker] = dataclasses.field(
        default=None, repr=False
    )
    # per-bucket SolverStats from the most recent update (the convergence-
    # adaptive driver's lane-efficiency telemetry; empty before any update)
    last_solver_stats: list = dataclasses.field(default_factory=list, repr=False)
    # multi-chip: shard each bucket's entity axis over these mesh axes
    # (entity solves are independent — no collectives); re-applied after
    # every offset rebuild
    mesh: Optional[object] = None
    mesh_axes: Optional[tuple] = None
    # per-entity coefficient variances from the local Hessian diagonals
    # (reference COMPUTE_VARIANCE; SingleNodeOptimizationProblem variances)
    compute_variances: bool = False
    # >= 2 overlaps that many bucket solves on worker threads (the async CD
    # schedule sets this; 0 = sequential, the bitwise-identical default)
    overlap_buckets: int = 0
    # base_offsets uploaded once; every device-plane update reuses it in the
    # jitted regroup instead of re-pushing a row-length host array
    _base_offsets_dev: Optional[jax.Array] = dataclasses.field(
        default=None, repr=False
    )

    supports_device_plane = True

    def _place(self, ds: RandomEffectDataset) -> RandomEffectDataset:
        if self.mesh is None:
            return ds
        from photon_ml_tpu.data.random_effect import place_dataset

        return place_dataset(ds, self.mesh, self.mesh_axes)

    def update_model(
        self, model: Optional[RandomEffectModel], residual_scores: np.ndarray
    ) -> RandomEffectModel:
        ds = self._place(
            self.dataset.update_offsets(self.base_offsets + residual_scores)
        )
        return self._train(ds, model)

    def update_model_device(
        self, model: Optional[RandomEffectModel], residual_scores: jax.Array
    ) -> RandomEffectModel:
        """Device-plane update: base + residual offsets are regrouped into
        the entity-grouped blocks by the precomputed (bucket, lane, slot)
        gather on device — the per-update host rebuild disappears."""
        if self._base_offsets_dev is None:
            self._base_offsets_dev = jnp.asarray(
                np.asarray(self.base_offsets, dtype=np.float32)
            )
        ds = self._place(
            self.dataset.update_offsets_device(
                _fused_residual_offsets(self._base_offsets_dev, residual_scores)
            )
        )
        return self._train(ds, model)

    def _train(
        self, ds: RandomEffectDataset, model: Optional[RandomEffectModel]
    ) -> RandomEffectModel:
        stats: list = []
        with span("re/train", buckets=len(ds.buckets), **mesh_attrs(self.mesh)):
            new_model, results = train_random_effects(
                ds, self.task, self.configuration, initial_model=model,
                compute_variances=self.compute_variances, stats_out=stats,
                overlap_buckets=self.overlap_buckets,
            )
        self.last_solver_stats = stats
        # entity lanes beyond the real ids (mesh padding) carry zero weights
        # and all-invalid projections: their solves are trivial, their
        # coefficients are forced to 0 by the proj_valid mask, and the
        # telemetry excludes them
        self.last_tracker = RandomEffectOptimizationTracker.from_results(
            results, real_counts=[len(ids) for ids in ds.entity_ids]
        )
        return new_model

    def score(self, model: RandomEffectModel) -> np.ndarray:
        return score_random_effects(model, self.dataset)

    def score_device(self, model: RandomEffectModel) -> jax.Array:
        return score_random_effects_device(model, self.dataset)
