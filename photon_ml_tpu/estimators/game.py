"""GameEstimator: sklearn-style fit() for GAME/GLMix models.

Reference parity: estimators/GameEstimator.scala:52 — fit(data, validation,
configs) builds per-coordinate datasets (prepareTrainingDataSets :292-343),
loss/optimizer per coordinate, runs CoordinateDescent, and evaluates
validation data per update; one fit per optimization configuration, best
model selected by the first validation evaluator.

TPU-native notes: dataset preparation (entity grouping, projection, ELL
building) happens once here — the analog of the reference's one-time
shuffles — producing device-resident blocks reused across configurations.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.algorithm.coordinate import (
    Coordinate,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.algorithm.coordinate_descent import (
    SCORE_PLANES,
    CoordinateDescent,
)
from photon_ml_tpu.algorithm.schedule import SCHEDULES
from photon_ml_tpu.algorithm.factored_random_effect import (
    FactoredRandomEffectCoordinate,
    MFOptimizationConfiguration,
)
from photon_ml_tpu.data.game_data import FeatureShard, GameData
from photon_ml_tpu.data.random_effect import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_ml_tpu.evaluation.evaluators import Evaluator, default_evaluator
from photon_ml_tpu.losses.objective import make_glm_objective
from photon_ml_tpu.losses.pointwise import loss_for_task
from photon_ml_tpu.models.game import CoordinateMeta, GameModel
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration
from photon_ml_tpu.parallel.mesh import mesh_attrs
from photon_ml_tpu.telemetry import span
from photon_ml_tpu.telemetry.span import barrier_over, upload
from photon_ml_tpu.types import TaskType

logger = logging.getLogger("photon_ml_tpu")


def _coordinate_regularization(model, coord) -> float:
    """One coordinate's regularization term 0.5*l2*||w||^2 + l1*||w||_1
    over its current model (reference getRegularizationTermValue). The
    weights come from the COORDINATE object (which carries any sweep/tuning
    overrides), not the estimator's base configs. All reductions run on
    device (sharded arrays reduce with XLA-inserted collectives); exactly
    one scalar reaches the host per call."""
    from photon_ml_tpu.algorithm.factored_random_effect import (
        FactoredRandomEffectCoordinate,
        FactoredRandomEffectModel,
    )
    from photon_ml_tpu.models.glm import GeneralizedLinearModel
    from photon_ml_tpu.models.random_effect import RandomEffectModel

    def term(a, opt):
        return 0.5 * opt.l2_weight * jnp.sum(a * a) + opt.l1_weight * jnp.sum(
            jnp.abs(a)
        )

    if isinstance(model, FactoredRandomEffectModel):
        assert isinstance(coord, FactoredRandomEffectCoordinate)
        total = sum(
            term(c, coord.re_configuration)
            for c in model.latent.coefficients
        )
        total = total + term(model.projection_matrix, coord.matrix_configuration)
        return float(total)
    opt = getattr(coord, "configuration", None)
    if opt is None:
        return 0.0
    if isinstance(model, GeneralizedLinearModel):
        return float(term(model.coefficients.means, opt))
    if isinstance(model, RandomEffectModel):
        return float(sum(term(c, opt) for c in model.coefficients))
    return 0.0


def _describe_config(cfg: GlmOptimizationConfiguration) -> str:
    return (
        f"{cfg.optimizer_config.optimizer.name}"
        f"(λ={cfg.regularization_weight}, {cfg.regularization.reg_type.name})"
    )


def _config_digest(overrides: Dict[str, GlmOptimizationConfiguration]) -> str:
    """Stable 8-hex fingerprint of a per-coordinate override map; part of
    the per-config checkpoint path so an edited sweep list cannot resume
    from a checkpoint trained under different settings."""
    import hashlib

    key = repr(sorted((cid, cfg) for cid, cfg in overrides.items()))
    return hashlib.sha1(key.encode()).hexdigest()[:8]


@dataclasses.dataclass(frozen=True)
class ParallelConfiguration:
    """Multi-chip layout for GAME training over a (data x feat) device grid.

    - Fixed-effect coordinates train through the grid-sharded sparse engine
      (parallel/grid_features.py): examples sharded over ``n_data`` devices,
      coefficients over ``n_feat`` (margins psum over feat, gradients over
      data) — the reference's treeAggregate+broadcast replaced by ICI
      collectives, with no chip ever holding the full coefficient vector.
    - Random-effect coordinates shard their entity blocks over ALL
      n_data*n_feat devices (independent per-entity solves, no collectives).

    The reference has no analog: Spark parallelism is implicit in the RDD
    runtime (GameEstimator.scala treeAggregateDepth is its only knob).
    """

    n_data: int
    n_feat: int = 1
    engine: str = "benes"  # grid tile engine: "benes" | "ell" | "fused"

    def build_mesh(self):
        from photon_ml_tpu.parallel.grid_features import grid_mesh

        return grid_mesh(self.n_data, self.n_feat)


@dataclasses.dataclass(frozen=True)
class FixedEffectCoordinateConfiguration:
    """Reference FixedEffectDataConfiguration + per-coordinate optimizer
    config (GameEstimator builds both from the CLI mini-languages)."""

    feature_shard: str
    optimizer: GlmOptimizationConfiguration = GlmOptimizationConfiguration()
    # sparse engine for the global problem: "auto" | "ell" | "benes" | "fused"
    # (GameData.sparse_features; "auto" routes large TPU problems through
    # the permutation engine)
    sparse_engine: str = "auto"


@dataclasses.dataclass(frozen=True)
class RandomEffectCoordinateConfiguration:
    feature_shard: str
    data: RandomEffectDataConfiguration
    optimizer: GlmOptimizationConfiguration = GlmOptimizationConfiguration()


@dataclasses.dataclass(frozen=True)
class FactoredRandomEffectCoordinateConfiguration:
    """Reference FactoredRandomEffectOptimizationProblem.scala:42: a latent
    RE problem + projection-matrix problem pair plus MF config."""

    feature_shard: str
    data: RandomEffectDataConfiguration
    mf: MFOptimizationConfiguration
    optimizer: GlmOptimizationConfiguration = GlmOptimizationConfiguration()
    matrix_optimizer: Optional[GlmOptimizationConfiguration] = None


CoordinateConfiguration = Union[
    FixedEffectCoordinateConfiguration,
    RandomEffectCoordinateConfiguration,
    FactoredRandomEffectCoordinateConfiguration,
]


@dataclasses.dataclass
class GameFit:
    model: GameModel
    validation_metric: Optional[float]
    objective_history: List[Tuple[str, float]]
    validation_history: List[Tuple[str, float]]


class GameEstimator:
    def __init__(
        self,
        task: TaskType,
        coordinates: Dict[str, CoordinateConfiguration],
        update_order: Optional[Sequence[str]] = None,
        num_outer_iterations: int = 1,
        evaluator: Optional[Evaluator] = None,
        normalization: Optional[Dict[str, NormalizationContext]] = None,
        intercept_indices: Optional[Dict[str, int]] = None,
        parallel: Optional[ParallelConfiguration] = None,
        extra_evaluators: Sequence[Evaluator] = (),
        compute_variance: bool = False,
        emitter: Optional[object] = None,
        score_plane: str = "device",
        schedule: str = "sync",
        staleness: int = 1,
    ) -> None:
        """``normalization``/``intercept_indices`` are per-feature-shard;
        they apply to fixed-effect coordinates (training runs in normalized
        space, coefficients are mapped back after each solve — reference
        prepareNormalizationContexts, GameEstimator.scala). Random-effect
        locals are index-map projected and train unnormalized.

        ``evaluator`` selects best models; ``extra_evaluators`` are
        additionally computed and logged per coordinate per CD iteration
        (the reference logs EVERY configured evaluator there,
        CoordinateDescent.scala:283-293) without affecting selection."""
        if not coordinates:
            raise ValueError("need at least one coordinate configuration")
        self.task = task
        self.coordinate_configs = dict(coordinates)
        self.update_order = list(update_order) if update_order else list(coordinates)
        self.num_outer_iterations = num_outer_iterations
        self.evaluator = evaluator or default_evaluator(task)
        self.extra_evaluators = list(extra_evaluators)
        self.normalization = dict(normalization or {})
        self.intercept_indices = dict(intercept_indices or {})
        self.parallel = parallel
        self._mesh = parallel.build_mesh() if parallel is not None else None
        # reference COMPUTE_VARIANCE (GameTrainingParams): attach 1/(H_jj+eps)
        # coefficient variances to FE and RE models (not the factored/MF
        # coordinate — random-projection variances don't back-project)
        self.compute_variance = compute_variance
        # optional event.EventEmitter for SolverStatsEvent telemetry from the
        # CD driver (adaptive random-effect lane efficiency)
        self.emitter = emitter
        # where the CD score plane lives: "device" keeps per-coordinate score
        # arrays resident on the training mesh with scalar-only host
        # transfers; "host" is the legacy numpy plane. Multi-controller runs
        # always use the host plane — its fetch_global collectives are the
        # proven cross-process ordering.
        if score_plane not in SCORE_PLANES:
            raise ValueError(
                f"score_plane must be one of {SCORE_PLANES}, got {score_plane!r}"
            )
        self.score_plane = score_plane
        # CD schedule: "sync" (default, bitwise-identical trajectories) or
        # "async" (bounded-staleness pipelined solves + RE bucket overlap on
        # the device plane). Multi-controller runs force sync, exactly like
        # they force the host score plane.
        if schedule not in SCHEDULES:
            raise ValueError(
                f"schedule must be one of {SCHEDULES}, got {schedule!r}"
            )
        if int(staleness) < 0:
            raise ValueError(f"staleness must be >= 0, got {staleness}")
        self.schedule = schedule
        self.staleness = int(staleness)
        # per-bucket SolverStats from the most recent resolve_coordinate call
        self.last_resolve_stats: list = []
        # TransferStats from the most recent _run_fit / resolve_coordinate
        self.last_transfer_stats = None
        self.last_resolve_transfers = None

    def _effective_score_plane(self) -> str:
        """Device plane requires fully-addressable score arrays; under a
        multi-controller runtime eager per-row ops on globally-sharded
        arrays are not safe, so fall back to the host plane (whose
        fetch_global collectives run in identical order on every process)."""
        if jax.process_count() > 1:
            return "host"
        return self.score_plane

    def _effective_schedule(self) -> str:
        """The async schedule pipelines eager per-row updates on the device
        score plane; under multi-controller (or whenever the effective
        plane is the host one) the sync loop's single global dispatch order
        is required, so async falls back to sync."""
        if self.schedule == "async" and self._effective_score_plane() != "device":
            return "sync"
        return self.schedule

    def _build_coordinate(
        self, cid: str, cfg: CoordinateConfiguration, data: GameData
    ) -> Coordinate:
        with span(
            "game/build_coordinate", coordinate=cid, kind=type(cfg).__name__,
            **mesh_attrs(self._mesh),
        ):
            return self._build_coordinate_impl(cid, cfg, data)

    def _build_coordinate_impl(
        self, cid: str, cfg: CoordinateConfiguration, data: GameData
    ) -> Coordinate:
        shard = data.feature_shards[cfg.feature_shard]
        if isinstance(cfg, FixedEffectCoordinateConfiguration):
            if self.parallel is not None:
                return self._build_grid_fixed_effect(cfg, data)
            features = data.sparse_features(cfg.feature_shard, engine=cfg.sparse_engine)
            labels, offsets, weights = upload("rows", lambda: tuple(
                jnp.asarray(a) for a in (data.labels, data.offsets, data.weights)
            ))
            labeled = LabeledData.create(
                features, labels, offsets=offsets, weights=weights,
                norm=self.normalization.get(cfg.feature_shard),
            )
            return FixedEffectCoordinate(
                data=labeled,
                task=self.task,
                configuration=cfg.optimizer,
                intercept_index=self.intercept_indices.get(cfg.feature_shard),
                compute_variances=self.compute_variance,
            )
        re_ds = build_random_effect_dataset(
            data.id_tags[cfg.data.random_effect_type],
            shard.rows,
            shard.cols,
            shard.vals,
            shard.dim,
            data.labels,
            cfg.data,
            offsets=data.offsets,
            weights=data.weights,
        )
        # computed unconditionally: the summary's device reductions are
        # collectives on sharded buckets, so they must run on every process
        # regardless of per-process log levels
        logger.info("[%s] %s", cid, re_ds.to_summary_string())
        mesh = None
        mesh_axes = None
        if self.parallel is not None:
            from photon_ml_tpu.data.random_effect import (
                pad_entities_to_multiple,
                place_dataset,
            )
            from photon_ml_tpu.parallel.grid_features import DATA_AXIS, FEAT_AXIS

            n_dev = self.parallel.n_data * self.parallel.n_feat
            mesh = self._mesh
            mesh_axes = (DATA_AXIS, FEAT_AXIS)
            # entity-axis sharding over every device of the grid — for the
            # factored coordinate too (its latent datasets derive from these
            # arrays, so the per-entity solves inherit the placement)
            padded = pad_entities_to_multiple(re_ds, n_dev)
            re_ds = dataclasses.replace(padded, buckets=upload(
                "re_bucket", lambda: place_dataset(padded, mesh, mesh_axes).buckets
            ))
        if isinstance(cfg, FactoredRandomEffectCoordinateConfiguration):
            return FactoredRandomEffectCoordinate(
                dataset=re_ds,
                task=self.task,
                re_configuration=cfg.optimizer,
                matrix_configuration=cfg.matrix_optimizer or cfg.optimizer,
                mf_configuration=cfg.mf,
                base_offsets=data.offsets,
                mesh=mesh,
                mesh_axes=mesh_axes,
            )
        return RandomEffectCoordinate(
            dataset=re_ds,
            task=self.task,
            configuration=cfg.optimizer,
            base_offsets=data.offsets,
            mesh=mesh,
            mesh_axes=mesh_axes,
            compute_variances=self.compute_variance,
        )

    def _build_grid_fixed_effect(
        self, cfg: "FixedEffectCoordinateConfiguration", data: GameData
    ) -> FixedEffectCoordinate:
        """Fixed effect over the (data x feat) device grid: features tiled
        through the grid engine, batch arrays padded + data-sharded, the
        normalization context padded on the feature axis. The coordinate
        trims back to real shapes at its boundary."""
        from photon_ml_tpu.parallel.grid_features import (
            grid_from_coo,
            shard_vector_data,
        )

        shard = data.feature_shards[cfg.feature_shard]
        n, d = data.num_rows, shard.dim
        gf = grid_from_coo(
            shard.rows, shard.cols, shard.vals, (n, d), self._mesh,
            engine=self.parallel.engine,
        )

        def pad_rows(a):
            out = np.zeros(gf.num_rows, dtype=np.float32)
            out[:n] = np.asarray(a, dtype=np.float32)
            return shard_vector_data(jnp.asarray(out), self._mesh)

        norm = self.normalization.get(cfg.feature_shard)
        if norm is not None and gf.dim != d:
            factor = norm.factor
            shift = norm.shift
            if factor is not None:
                factor = jnp.pad(
                    jnp.asarray(factor), (0, gf.dim - d), constant_values=1.0
                )
            if shift is not None:
                shift = jnp.pad(jnp.asarray(shift), (0, gf.dim - d))
            norm = norm.replace(factor=factor, shift=shift)

        labels, offsets, weights = upload("rows", lambda: tuple(
            pad_rows(a) for a in (data.labels, data.offsets, data.weights)
        ))
        labeled = LabeledData(
            features=gf, labels=labels, offsets=offsets, weights=weights, norm=norm,
        )
        return FixedEffectCoordinate(
            data=labeled,
            task=self.task,
            configuration=cfg.optimizer,
            intercept_index=self.intercept_indices.get(cfg.feature_shard),
            num_real_rows=n,
            num_real_cols=d,
            compute_variances=self.compute_variance,
        )

    def _meta(self) -> Dict[str, CoordinateMeta]:
        meta = {}
        for cid, cfg in self.coordinate_configs.items():
            if isinstance(cfg, FixedEffectCoordinateConfiguration):
                meta[cid] = CoordinateMeta(
                    feature_shard=cfg.feature_shard,
                    sparse_engine=cfg.sparse_engine,
                )
            else:
                meta[cid] = CoordinateMeta(
                    feature_shard=cfg.feature_shard,
                    random_effect_type=cfg.data.random_effect_type,
                )
        return meta

    @staticmethod
    def _check_resume_compatible(
        models: Dict[str, object],
        coordinates: Dict[str, Coordinate],
        require_all: bool = True,
    ) -> None:
        """Fail fast (with a clear message) when a checkpoint's layout does
        not match the datasets rebuilt from the current data/config."""
        from photon_ml_tpu.models.glm import GeneralizedLinearModel
        from photon_ml_tpu.models.random_effect import RandomEffectModel

        problems = []
        for cid, model in models.items():
            coord = coordinates.get(cid)
            if coord is None:
                problems.append(f"{cid}: not in current configuration")
                continue
            if isinstance(model, GeneralizedLinearModel):
                from photon_ml_tpu.streaming.coordinate import (
                    StreamingFixedEffectCoordinate,
                )

                if not isinstance(
                    coord,
                    (FixedEffectCoordinate, StreamingFixedEffectCoordinate),
                ):
                    problems.append(
                        f"{cid}: checkpoint holds a fixed-effect model but "
                        "the coordinate is now configured as "
                        f"{type(coord).__name__}"
                    )
                    continue
                # parallel layouts pad the coordinate's feature axis;
                # checkpoints carry real-dim models (streaming coordinates
                # always speak real dims)
                if isinstance(coord, StreamingFixedEffectCoordinate):
                    want = coord.dim
                else:
                    want = coord.num_real_cols or coord.data.dim
                if model.dim != want:
                    problems.append(
                        f"{cid}: checkpoint dim {model.dim} != data dim {want}"
                    )
            else:
                latent = getattr(model, "latent", model)
                if not isinstance(latent, RandomEffectModel):
                    continue
                ds = coord.dataset
                if latent.entity_ids != ds.entity_ids:
                    problems.append(
                        f"{cid}: checkpoint entity layout differs from the "
                        "dataset rebuilt from the current data/config"
                    )
        if require_all and set(coordinates) - set(models):
            missing = sorted(set(coordinates) - set(models))
            problems.append(f"coordinates missing from checkpoint: {missing}")
        if problems:
            raise ValueError(
                "checkpoint is incompatible with this run — it was written "
                "for different data or configuration:\n  "
                + "\n  ".join(problems)
            )

    def resolve_coordinate(
        self,
        cid: str,
        data: GameData,
        models: Dict[str, object],
        initial_model: object = "auto",
    ):
        """Warm-started re-solve of ONE coordinate against ``data`` — the
        single-coordinate slice of a CD outer iteration, exposed for the
        nearline incremental trainer.

        Builds only this coordinate's dataset over ``data``, scores every
        OTHER coordinate's current model as the residual offset (standard CD
        residual algebra), and runs one ``update_model``. For a random-effect
        coordinate the warm start is re-aligned onto the fresh dataset's
        entity layout by id (``align_warm_start``) — entities absent from
        ``data`` are untouched by construction because the dataset only
        contains the entities present in it; entities absent from the old
        model start from zero. Returns the re-solved sub-model in the new
        dataset's layout.
        """
        cfg = self.coordinate_configs.get(cid)
        if cfg is None:
            raise ValueError(
                f"unknown coordinate {cid!r}; have {sorted(self.coordinate_configs)}"
            )
        if isinstance(cfg, FactoredRandomEffectCoordinateConfiguration):
            raise ValueError(
                f"coordinate {cid!r} is factored — single-coordinate re-solve "
                "supports fixed-effect and plain random-effect coordinates"
            )
        with self._grid_barrier(), span(
            "game/resolve_coordinate", coordinate=cid, num_rows=data.num_rows
        ):
            return self._resolve_coordinate_impl(
                cid, cfg, data, models, initial_model
            )

    def _resolve_coordinate_impl(self, cid, cfg, data, models, initial_model):
        coord = self._build_coordinate(cid, cfg, data)
        meta = self._meta()
        others = {
            c: m for c, m in models.items() if c != cid and m is not None
        }
        if others:
            gm = GameModel(
                models=others,
                meta={c: meta[c] for c in others},
                task=self.task,
            )
            residual = np.asarray(gm.score(data), dtype=np.float32)
        else:
            residual = np.zeros(data.num_rows, dtype=np.float32)
        model0 = models.get(cid) if initial_model == "auto" else initial_model
        if isinstance(coord, RandomEffectCoordinate) and model0 is not None:
            from photon_ml_tpu.estimators.random_effect import align_warm_start

            model0 = align_warm_start(model0, coord.dataset)
        from photon_ml_tpu.opt.tracking import TransferStats

        effective_plane = self._effective_score_plane()
        transfers = TransferStats(
            score_plane=effective_plane, num_rows=data.num_rows
        )
        transfers.coordinate_updates = 1
        if effective_plane == "device" and coord.supports_device_plane:
            # one residual upload; the offset regroup onto the coordinate's
            # padded blocks happens on device (no further row transfers)
            transfers.record_h2d()
            transfers.device_plane_updates = 1
            updated = coord.update_model_device(model0, jnp.asarray(residual))
        else:
            transfers.record_h2d()
            updated = coord.update_model(model0, residual)
        self.last_resolve_transfers = transfers
        # warm-started nearline re-solves have the largest iteration skew —
        # surface the adaptive driver's lane telemetry to the caller
        self.last_resolve_stats = list(getattr(coord, "last_solver_stats", []))
        if self.emitter is not None and self.last_resolve_stats:
            from photon_ml_tpu.event import SolverStatsEvent

            for s in self.last_resolve_stats:
                self.emitter.send_event(SolverStatsEvent.from_stats(cid, s))
        return updated

    def fit(
        self,
        data: GameData,
        validation_data: Optional[GameData] = None,
        checkpoint_dir: Optional[str] = None,
        initial_models: Optional[Dict[str, object]] = None,
        progress: Optional[object] = None,
    ) -> GameFit:
        """With ``checkpoint_dir``, training state is written atomically
        after every outer CD iteration and an existing checkpoint there is
        resumed automatically (skipping completed iterations) — see
        photon_ml_tpu.checkpoint. ``initial_models`` warm-starts coordinates
        (reference warmStartModels across tuning trials,
        cli/game/training/Driver.scala:484-501); a resumed checkpoint takes
        precedence. ``progress`` is an optional
        :class:`~photon_ml_tpu.telemetry.progress.ConvergenceTracker`; None
        (the default) leaves training bitwise-identical."""
        coordinates = {
            cid: self._build_coordinate(cid, cfg, data)
            for cid, cfg in self.coordinate_configs.items()
        }
        return self._run_fit(
            coordinates, data, validation_data, checkpoint_dir, initial_models,
            progress=progress,
        )

    def fit_streaming(
        self,
        source,
        validation_data: Optional[GameData] = None,
        checkpoint_dir: Optional[str] = None,
        initial_models: Optional[Dict[str, object]] = None,
        prefetch_depth: int = 2,
        mode: str = "full",
        stochastic_epochs: int = 5,
        stochastic_chunk_iters: int = 4,
        blocks_per_update: int = 1,
        seed: int = 0,
        gap_schedule: bool = False,
        resident_blocks: int = 0,
        resident_bytes: Optional[int] = None,
        progress: Optional[object] = None,
        cluster: Optional[object] = None,
    ) -> GameFit:
        """Out-of-core ``fit``: fixed-effect coordinates stream fixed-shape
        blocks from a :class:`~photon_ml_tpu.streaming.StreamingSource`
        instead of holding the design matrix in memory.

        One streamed setup pass accumulates the per-row scalar planes
        (labels/offsets/weights/id tags — O(n) scalars, not features) and
        the per-entity COO of random-effect shards, so RE coordinates run
        through the existing cost-sorted bucket packing unchanged. The FE
        feature payload — the memory-dominant term — never materializes:
        each CD update/score re-streams it, with host staging bounded by
        ``prefetch_depth × block bytes``.

        ``mode='full'`` is the exact full-batch streamed solve (same
        optimum as in-memory, the default); ``mode='stochastic'`` visits
        shuffled block groups per epoch on the resumable solver seam —
        gate it on held-out metric parity before trusting it.
        ``gap_schedule=True`` (stochastic only) replaces the blind shuffle
        with duality-gap-guided block selection (docs/SCALING.md).

        ``resident_blocks``/``resident_bytes`` cap a device-resident set of
        top-gap blocks whose uploads persist across streamed passes — the
        HBM level of the residency hierarchy (docs/SCALING.md "Residency
        hierarchy"). Warm passes then re-upload only the non-resident
        remainder; the solve trajectory is unchanged (identical visit
        order, only transfer volume drops). Requires ``mode='full'`` or
        ``gap_schedule=True``, and no ``cluster``.

        ``cluster`` (a ``parallel.cluster.ClusterPlane`` or bare
        ``ClusterCoordinator``) runs the fixed-effect solve data-parallel
        across hosts: every streamed pass becomes a distributed allreduce
        over the workers' assigned block shares, while random-effect
        coordinates stay entity-partitioned on this host (per-entity
        solves never cross hosts — the GAME structure makes RE
        embarrassingly parallel). Requires ``mode='full'`` and exactly one
        fixed-effect coordinate (one cluster drives one block plan).
        """
        from photon_ml_tpu.streaming.coordinate import (
            StreamingFixedEffectCoordinate,
        )

        if self.parallel is not None:
            raise ValueError(
                "streaming training does not compose with the device-grid "
                "parallel layout yet (multi-host streaming is roadmap work)"
            )
        if self.compute_variance:
            raise ValueError(
                "streaming training cannot compute coefficient variances "
                "(needs a second Hessian-diagonal pass; train in-memory)"
            )
        fe_cfgs = {
            cid: cfg
            for cid, cfg in self.coordinate_configs.items()
            if isinstance(cfg, FixedEffectCoordinateConfiguration)
        }
        for cid, cfg in fe_cfgs.items():
            if self.normalization.get(cfg.feature_shard) is not None:
                raise ValueError(
                    f"streaming coordinate {cid!r}: normalization requires "
                    "a streamed feature-stats pass (not implemented); use "
                    "--normalization-type NONE or train in-memory"
                )
        if cluster is not None:
            if mode != "full":
                raise ValueError(
                    "cluster training requires mode='full' (the distributed "
                    "pass sums exact per-host partials)"
                )
            if len(fe_cfgs) != 1:
                raise ValueError(
                    "cluster training requires exactly one fixed-effect "
                    f"coordinate, config has {sorted(fe_cfgs) or 'none'}"
                )
        re_shards = sorted({
            cfg.feature_shard
            for cid, cfg in self.coordinate_configs.items()
            if cid not in fe_cfgs
        })
        planes = source.row_planes(coo_shards=re_shards)
        data = GameData(
            labels=planes.labels,
            feature_shards={
                sid: FeatureShard(rows=r, cols=c, vals=v, dim=d)
                for sid, (r, c, v, d) in planes.shard_coo.items()
            },
            id_tags=planes.id_tags,
            offsets=planes.offsets,
            weights=planes.weights,
        )
        coordinates: Dict[str, Coordinate] = {}
        for cid, cfg in self.coordinate_configs.items():
            if cid in fe_cfgs:
                coordinates[cid] = StreamingFixedEffectCoordinate(
                    source=source,
                    shard_id=cfg.feature_shard,
                    task=self.task,
                    configuration=cfg.optimizer,
                    prefetch_depth=prefetch_depth,
                    mode=mode,
                    epochs=stochastic_epochs,
                    chunk_iters=stochastic_chunk_iters,
                    blocks_per_update=blocks_per_update,
                    seed=seed,
                    gap_schedule=gap_schedule,
                    resident_blocks=resident_blocks,
                    resident_bytes=resident_bytes,
                    # convergence plane: per-block loss/grad/gap probes run
                    # only when a tracker is attached (bitwise contract)
                    collect_block_stats=progress is not None,
                    cluster=cluster,
                )
            else:
                coordinates[cid] = self._build_coordinate(cid, cfg, data)
        return self._run_fit(
            coordinates, data, validation_data, checkpoint_dir, initial_models,
            progress=progress,
        )

    def fit_multiple(
        self,
        data: GameData,
        validation_data: Optional[GameData] = None,
        configs: Sequence[Dict[str, GlmOptimizationConfiguration]] = (),
        warm_start: bool = True,
        checkpoint_dir: Optional[str] = None,
    ) -> List[GameFit]:
        """One fit per model configuration — the reference's
        ``fit(data, validation, Seq[GameModelOptimizationConfiguration])``
        (GameEstimator.scala:175-217), which trains one GAME model per swept
        configuration and leaves best-model selection to the caller
        (``select_best_fit`` = Driver.scala:356 selectBestModel).

        Each entry of ``configs`` maps coordinate id → per-coordinate
        optimizer configuration; coordinates absent from an entry keep the
        estimator's configured optimizer. The expensive dataset preparation
        (entity grouping, projection, routing) happens ONCE and is shared
        by every fit — only the solver configuration changes per run (the
        analog of the reference reusing prepared trainingDataSets across
        the config sequence). ``warm_start`` seeds each fit with the
        previous fit's models. ``checkpoint_dir`` gets one subdirectory per
        configuration, keyed by index AND a digest of the override map
        (``config-000-1a2b3c4d``) so a resume after the sweep list was
        edited retrains instead of silently returning a model trained
        under different settings.
        """
        base = {
            cid: self._build_coordinate(cid, cfg, data)
            for cid, cfg in self.coordinate_configs.items()
        }
        if not configs:
            configs = [{}]
        fits: List[GameFit] = []
        prev_models: Optional[Dict[str, object]] = None
        for i, overrides in enumerate(configs):
            unknown = set(overrides) - set(base)
            if unknown:
                raise ValueError(
                    f"config {i} names unknown coordinates: {sorted(unknown)}"
                )
            coords = {
                cid: (
                    self._replace_optimizer(coord, overrides[cid])
                    if cid in overrides
                    else coord
                )
                for cid, coord in base.items()
            }
            logger.info(
                "fit %d/%d with config overrides: %s", i + 1, len(configs),
                {c: _describe_config(v) for c, v in overrides.items()} or "(defaults)",
            )
            fit = self._run_fit(
                coords,
                data,
                validation_data,
                (
                    None
                    if checkpoint_dir is None
                    else f"{checkpoint_dir}/config-{i:03d}-{_config_digest(overrides)}"
                ),
                prev_models if warm_start else None,
            )
            fits.append(fit)
            if warm_start:
                prev_models = fit.model.models
        return fits

    def select_best_fit(self, fits: Sequence[GameFit]) -> Optional[int]:
        """Index of the fit the validation evaluator ranks best (reference
        Driver.scala:356 selectBestModel — reduce by the first evaluator's
        betterThan); None when no fit carries a validation metric, like the
        reference's reduceOption on an empty evaluation sequence."""
        best: Optional[int] = None
        for i, fit in enumerate(fits):
            if fit.validation_metric is None:
                continue
            if best is None or self.evaluator.better_than(
                fit.validation_metric, fits[best].validation_metric
            ):
                best = i
        return best

    @staticmethod
    def _replace_optimizer(
        coord: Coordinate, opt: GlmOptimizationConfiguration
    ) -> Coordinate:
        """A coordinate with the same (device-resident) dataset but a new
        optimizer configuration. For factored coordinates the projection-
        matrix solve follows the sweep only when it was sharing the RE
        configuration; a separately-configured matrix_optimizer is kept."""
        if isinstance(coord, FactoredRandomEffectCoordinate):
            shared = coord.matrix_configuration == coord.re_configuration
            return dataclasses.replace(
                coord,
                re_configuration=opt,
                matrix_configuration=(
                    opt if shared else coord.matrix_configuration
                ),
            )
        return dataclasses.replace(coord, configuration=opt)

    def _grid_barrier(self):
        """While open, ``device_sync`` spans wait for every device of the
        grid (for the default device where there is none)."""
        return barrier_over(
            () if self._mesh is None else self._mesh.devices.ravel().tolist()
        )

    def _run_fit(self, *args, **kwargs) -> GameFit:
        with self._grid_barrier():
            return self._run_fit_impl(*args, **kwargs)

    def _run_fit_impl(
        self,
        coordinates: Dict[str, Coordinate],
        data: GameData,
        validation_data: Optional[GameData],
        checkpoint_dir: Optional[str],
        initial_models: Optional[Dict[str, object]],
        progress: Optional[object] = None,
    ) -> GameFit:
        # everything a fit sets up before coordinate descent runs: the label,
        # weight and offset uploads, the objective / regulariser / validation
        # closures, a new CoordinateDescent, the checkpoint to resume from
        with span("game/prepare_fit", coordinates=len(coordinates)):
            meta = self._meta()

            loss = loss_for_task(self.task)
            labels = jnp.asarray(data.labels)
            weights = jnp.asarray(data.weights)
            offsets = jnp.asarray(data.offsets)

            def training_objective(total_scores) -> float:
                # accepts the device plane's running total (jax.Array) or the
                # host plane's numpy sum; exactly ONE scalar crosses to the host
                z = offsets + jnp.asarray(total_scores)
                terms = loss.value(z, labels)
                return float(jnp.sum(jnp.where(weights > 0, weights * terms, 0.0)))

            # per-coordinate cache keyed by model identity (strong ref, so an id
            # is never reused while cached): only the coordinate that just
            # updated recomputes its term
            reg_cache: Dict[str, Tuple[object, float]] = {}

            def regularization_term(models: Dict[str, object]) -> float:
                """Σ per-coordinate 0.5*l2*||w||^2 + l1*||w||_1 over the current
                models (reference getRegularizationTermValue, logged per update
                CoordinateDescent.scala:247-258). Weights come from the built
                Coordinate objects, which carry sweep/tuning overrides."""
                total = 0.0
                for cid, m in models.items():
                    coord = coordinates.get(cid)
                    if coord is None:
                        continue
                    cached = reg_cache.get(cid)
                    if cached is None or cached[0] is not m:
                        reg_cache[cid] = (m, _coordinate_regularization(m, coord))
                    total += reg_cache[cid][1]
                return total

            validate = None
            if validation_data is not None:
                def validate(models: Dict[str, object]) -> float:
                    # scored on the device against the held-out indexes the
                    # data set keeps; one scalar crosses back an evaluator
                    gm = GameModel(models=dict(models), meta=meta, task=self.task)
                    labels_v, weights_v, offsets_v = validation_data.device_rows()
                    scores = gm.score_device(validation_data) + offsets_v
                    primary = self.evaluator.evaluate(scores, labels_v, weights_v)
                    if self.extra_evaluators:
                        # reference CoordinateDescent.scala:283-293: every
                        # configured evaluator is computed and logged per
                        # coordinate update; only the first drives selection
                        extras = {
                            ev.name: ev.evaluate(scores, labels_v, weights_v)
                            for ev in self.extra_evaluators
                        }
                        logger.info(
                            "validation metrics: %s=%.6f %s",
                            self.evaluator.name, primary,
                            " ".join(f"{k}={v:.6f}" for k, v in extras.items()),
                        )
                    return primary

            schedule = self._effective_schedule()
            # the async schedule's RE leg: overlap bucket solves inside each
            # random-effect coordinate (0 restores the sequential, bitwise-
            # identical path — set every run so shared built coordinates are
            # correct for whichever schedule this fit uses)
            for coord in coordinates.values():
                if hasattr(coord, "overlap_buckets"):
                    coord.overlap_buckets = 2 if schedule == "async" else 0

            cd = CoordinateDescent(
                coordinates,
                num_rows=data.num_rows,
                update_order=self.update_order,
                training_objective=training_objective,
                regularization_term=regularization_term,
                validate=validate,
                validation_better_than=self.evaluator.better_than,
                emitter=self.emitter,
                score_plane=self._effective_score_plane(),
                schedule=schedule,
                staleness=self.staleness,
                progress=progress,
            )

            start_iteration = 0
            initial_best = None
            on_iteration_end = None
            prior_objective_history: List[Tuple[str, float]] = []
            prior_validation_history: List[Tuple[str, float]] = []
            if initial_models is not None:
                # warm start may cover a subset of coordinates
                self._check_resume_compatible(
                    initial_models, coordinates, require_all=False
                )
            if checkpoint_dir is not None:
                from photon_ml_tpu import checkpoint as ckpt

                if ckpt.has_checkpoint(checkpoint_dir):
                    initial_models, state, best = ckpt.load_training_checkpoint(
                        checkpoint_dir
                    )
                    self._check_resume_compatible(initial_models, coordinates)
                    start_iteration = int(state["completed_iterations"])
                    if best is not None and state.get("best_metric") is not None:
                        initial_best = (best, float(state["best_metric"]))
                    prior_objective_history = [
                        tuple(x) for x in state.get("objective_history", [])
                    ]
                    prior_validation_history = [
                        tuple(x) for x in state.get("validation_history", [])
                    ]
                    logger.info(
                        "resuming from checkpoint %s at outer iteration %d",
                        checkpoint_dir, start_iteration,
                    )

                def on_iteration_end(outer: int, running) -> None:
                    ckpt.save_training_checkpoint(
                        checkpoint_dir,
                        running.models,
                        state={
                            "completed_iterations": outer + 1,
                            "best_metric": running.best_metric,
                            # full histories so a second resume stays complete
                            "objective_history": prior_objective_history
                            + running.objective_history,
                            "validation_history": prior_validation_history
                            + running.validation_history,
                        },
                        best_models=(
                            running.best_models if validate is not None else None
                        ),
                    )

        with span(
            "game/fit",
            coordinates=len(coordinates),
            num_rows=data.num_rows,
            score_plane=cd.score_plane,
        ):
            result = cd.run(
                self.num_outer_iterations,
                initial_models=initial_models,
                start_iteration=start_iteration,
                initial_best=initial_best,
                on_iteration_end=on_iteration_end,
            )
        self.last_transfer_stats = cd.transfer_stats
        model = GameModel(models=result.best_models, meta=meta, task=self.task)
        return GameFit(
            model=model,
            validation_metric=result.best_metric,
            objective_history=prior_objective_history + result.objective_history,
            validation_history=prior_validation_history + result.validation_history,
        )
