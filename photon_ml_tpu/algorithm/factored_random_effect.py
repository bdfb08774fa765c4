"""Factored random-effect coordinate: per-entity latent factors + a shared
projection matrix, trained by alternating solves.

Reference parity: algorithm/FactoredRandomEffectCoordinate.scala:40 — the
alternating loop (:112-146) interleaves (a) a per-entity random-effect solve
in the k-dimensional latent space and (b) a global solve for the projection
matrix B treated as one (d·k)-coefficient GLM over Kronecker-product features
kron(x, latent) (:227-280); FactoredRandomEffectOptimizationProblem.scala:42
pairs the two problems; MFOptimizationConfiguration.scala:29 is the
``numLatentFactors,numIterations`` config.

TPU-native design: the per-entity data stays in the index-map-projected
blocks of the RandomEffectDataset. Step (a) projects each bucket through B on
device (one einsum: X @ B[proj_indices]) and reuses the vmap'd RE trainer in
latent space. Step (b) never materializes kron(x, v): :class:`KronFeatures`
implements the three linear maps (matvec / rmatvec / rmatvec_sq) of the
implicit [n, d·k] design matrix as fused einsums + one scatter-add into the
[d, k] gradient — so the existing L-BFGS/TRON solvers run unchanged over
vec(B).

Both steps are kept programs: ``_project`` (the projection's einsums; the
buckets and B are arguments) is one jitted function for the process, and
``_matrix_solve_program`` builds one jitted solve per (task, optimizer
settings, ``use_l1``) in a cache of its own — not in ``train_glm``'s single
``_solve_program`` slot, which the fixed effect of the same fit holds. The
latent solves go through ``train_random_effects`` and its ``_re_programs``.
So after a first update nothing here traces, lowers or compiles again
(``jit.traces.mf_project``, ``jit.traces.mf_matrix_solve``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from photon_ml_tpu.algorithm.coordinate import Coordinate, _fused_residual_offsets
from photon_ml_tpu.data.random_effect import RandomEffectDataset
from photon_ml_tpu.estimators.random_effect import (
    score_random_effects,
    score_random_effects_device,
    train_random_effects,
)
from photon_ml_tpu.losses.objective import make_glm_objective
from photon_ml_tpu.losses.pointwise import loss_for_task
from photon_ml_tpu.models.random_effect import RandomEffectModel
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration, OptimizerConfig
from photon_ml_tpu.opt.solve import solve, solver_kind
from photon_ml_tpu.opt.state import SolveResult
from photon_ml_tpu.telemetry import note_jit_trace
from photon_ml_tpu.telemetry.span import get_tracer, span
from photon_ml_tpu.types import TaskType


@dataclasses.dataclass(frozen=True)
class MFOptimizationConfiguration:
    """Reference MFOptimizationConfiguration.scala:29
    (``numLatentFactors,numIterations``)."""

    num_latent_factors: int
    num_iterations: int = 1
    seed: int = 0

    def __post_init__(self) -> None:
        if self.num_latent_factors < 1:
            raise ValueError("num_latent_factors must be >= 1")
        if self.num_iterations < 1:
            raise ValueError("num_iterations must be >= 1")


@struct.dataclass
class KronFeatures:
    """Implicit design matrix of the projection-matrix solve.

    Row (e, s) of bucket b has features kron(latent[e], x[e, s]) laid out as
    vec(B) with B of shape [d_global, k]: coefficient (c, j) multiplies
    x_value-at-global-col-c times latent[e, j]. Bucket blocks are carried as
    parallel lists; rows are the concatenation of all buckets' flattened
    [E*S] axes (padding rows have weight 0 upstream).
    """

    xs: List[jax.Array]        # per bucket [E, S, D] local features
    pidxs: List[jax.Array]     # per bucket [E, D] global col per local col
    latents: List[jax.Array]   # per bucket [E, k]
    d_global: int = struct.field(pytree_node=False)
    k: int = struct.field(pytree_node=False)

    @property
    def num_rows(self) -> int:
        return sum(x.shape[0] * x.shape[1] for x in self.xs)

    @property
    def dim(self) -> int:
        return self.d_global * self.k

    def matvec(self, w: jax.Array) -> jax.Array:
        B = w.reshape(self.d_global, self.k)
        outs = []
        for x, pidx, v in zip(self.xs, self.pidxs, self.latents):
            # z[e,s] = x[e,s,:] . (B[pidx[e]] @ v[e]); padding cols have
            # x == 0 so their (arbitrary) B[0] gather contributes nothing
            z = jnp.einsum("esd,edk,ek->es", x, B[pidx], v)
            outs.append(z.reshape(-1))
        return jnp.concatenate(outs)

    def rmatvec(self, c: jax.Array) -> jax.Array:
        grad = jnp.zeros((self.d_global, self.k), dtype=c.dtype)
        start = 0
        for x, pidx, v in zip(self.xs, self.pidxs, self.latents):
            e_n, s_n = x.shape[0], x.shape[1]
            cb = c[start : start + e_n * s_n].reshape(e_n, s_n)
            start += e_n * s_n
            contrib = jnp.einsum("es,esd,ek->edk", cb, x, v)
            grad = grad.at[pidx].add(contrib)
        return grad.reshape(-1)

    def rmatvec_sq(self, c: jax.Array) -> jax.Array:
        out = jnp.zeros((self.d_global, self.k), dtype=c.dtype)
        start = 0
        for x, pidx, v in zip(self.xs, self.pidxs, self.latents):
            e_n, s_n = x.shape[0], x.shape[1]
            cb = c[start : start + e_n * s_n].reshape(e_n, s_n)
            start += e_n * s_n
            contrib = jnp.einsum("es,esd,ek->edk", cb, x * x, v * v)
            out = out.at[pidx].add(contrib)
        return out.reshape(-1)

    def row_norms_sq(self) -> jax.Array:
        outs = []
        for x, v in zip(self.xs, self.latents):
            # ||kron(v_e, x_es)||^2 = ||x_es||^2 * ||v_e||^2
            xn = jnp.sum(x * x, axis=-1)
            vn = jnp.sum(v * v, axis=-1)
            outs.append((xn * vn[:, None]).reshape(-1))
        return jnp.concatenate(outs)


@dataclasses.dataclass
class FactoredRandomEffectModel:
    """Latent per-entity factors + shared projection matrix (reference
    model/FactoredRandomEffectModel.scala:33). The effective per-entity
    coefficient vector in the ORIGINAL space is B @ latent_e."""

    random_effect_type: str
    task: TaskType
    latent: RandomEffectModel          # coefficients are [E, k] latent factors
    projection_matrix: jax.Array       # [d_global, k]

    @property
    def num_latent_factors(self) -> int:
        return int(self.projection_matrix.shape[1])

    def to_summary_string(self) -> str:
        """Reference Summarizable.toSummaryString (FactoredRandomEffectModel)."""
        return (
            f"factored random effect '{self.random_effect_type}': "
            f"{self.latent.num_entities} entities x "
            f"{self.num_latent_factors} latent factors, projection matrix "
            f"[{int(self.projection_matrix.shape[0])}, "
            f"{self.num_latent_factors}]"
        )

    def coefficients_for(self, entity_id: str) -> Optional[dict]:
        """Dense original-space coefficients w = B @ latent for one entity."""
        loc = self.latent.entity_to_loc.get(str(entity_id))
        if loc is None:
            return None
        b, e = loc
        v = np.asarray(self.latent.coefficients[b][e])
        w = np.asarray(self.projection_matrix) @ v
        return {int(i): float(x) for i, x in enumerate(w)}


@jax.jit
def _project(xs, pidxs, passives, B):
    """Step (a)'s input as one program: every bucket's active block and
    passive rows projected through B, X_latent[e,s] = B[pidx[e]]^T x[e,s],
    with the identity column layout of the k-dimensional latent space.
    ``passives`` holds a bucket's (X, entity_index) or None. The same
    function object for the life of the process, the buckets and B its
    arguments: an alternation, an update and a fit after the first dispatch
    what the first compiled."""
    note_jit_trace("mf_project")  # fires only on a (re)trace
    k = B.shape[1]
    out = []
    for x, pidx, passive in zip(xs, pidxs, passives):
        Bg = B[pidx]  # [E, D, k]; padding cols have x == 0
        e_n = x.shape[0]
        out.append((
            jnp.einsum("esd,edk->esk", x, Bg),
            None if passive is None
            else jnp.einsum("pd,pdk->pk", passive[0], Bg[passive[1]]),
            jnp.tile(jnp.arange(k, dtype=jnp.int32), (e_n, 1)),
            jnp.ones((e_n, k), dtype=bool),
        ))
    return out


def _latent_dataset(
    dataset: RandomEffectDataset, B: jax.Array
) -> RandomEffectDataset:
    """Project every bucket into the latent space of B (step (a) input):
    X_latent[e,s] = B[pidx[e]]^T x[e,s].

    The returned dataset's "global" space IS the k-dim latent space (identity
    projection, global_dim=k), so the latent RandomEffectModel trained on it
    exports honest {latent_axis: factor} maps rather than pretending its
    coordinates are original features.
    """
    from photon_ml_tpu.projector import ProjectorType

    projected = _project(
        [b.X for b in dataset.buckets],
        [b.proj_indices for b in dataset.buckets],
        [None if p is None else (p.X, p.entity_index) for p in dataset.passive],
        B,
    )
    new_buckets = [
        bucket.replace(X=Xl, proj_indices=pidx, proj_valid=pval)
        for bucket, (Xl, _, pidx, pval) in zip(dataset.buckets, projected)
    ]
    new_passive = [
        None if p is None else p.replace(X=Xp)
        for p, (_, Xp, _, _) in zip(dataset.passive, projected)
    ]
    return dataclasses.replace(
        dataset,
        buckets=new_buckets,
        passive=new_passive,
        global_dim=int(B.shape[1]),
        config=dataclasses.replace(
            dataset.config, projector=ProjectorType.IDENTITY, projected_dim=None
        ),
    )


@functools.lru_cache(maxsize=None)
def _matrix_solve_program(
    task: TaskType, optimizer_config: OptimizerConfig, use_l1: bool
) -> Callable[..., SolveResult]:
    """The jitted step (b) of one static key: ``(B0, xs, pidxs, latents,
    labels, offsets, weights, l2, l1) -> SolveResult`` over vec(B), the
    per-bucket blocks as lists. Kept for the process in this cache of its
    own: ``train_glm``'s ``_solve_program`` keeps ONE program (a kept
    program keeps its device reservation), and in a GAME fit that one is the
    fixed effect's — a matrix solve routed through it would evict it at
    every update. Everything that varies between calls is an argument, the
    regularization weights among them, so the key holds the task and the
    optimizer's settings."""
    objective = make_glm_objective(loss_for_task(task))
    configuration = GlmOptimizationConfiguration(optimizer_config=optimizer_config)
    kind = solver_kind(configuration, 1.0 if use_l1 else 0.0)

    def mf_matrix_solve(B0, xs, pidxs, latents, labels, offsets, weights, l2, l1):
        note_jit_trace("mf_matrix_solve", kind)  # fires only on a (re)trace
        feats = KronFeatures(
            xs=xs, pidxs=pidxs, latents=latents,
            d_global=B0.shape[0], k=B0.shape[1],
        )
        data = LabeledData(
            features=feats,
            labels=jnp.concatenate([a.reshape(-1) for a in labels]),
            offsets=jnp.concatenate([a.reshape(-1) for a in offsets]),
            weights=jnp.concatenate([a.reshape(-1) for a in weights]),
            norm=None,
        )
        return solve(
            objective, B0.reshape(-1), data, configuration,
            l2_weight=l2, l1_weight=l1 if use_l1 else 0.0,
        )

    return jax.jit(mf_matrix_solve)


@dataclasses.dataclass
class FactoredRandomEffectCoordinate(Coordinate):
    """Alternating MF-style coordinate (reference
    FactoredRandomEffectCoordinate.scala:40). On the device score plane the
    residual is regrouped into the entity blocks on the device and the
    latent model is scored over the B-projected blocks there, as the
    random-effect coordinate does: no row-length array crosses to the host.
    The host methods give the same model."""

    dataset: RandomEffectDataset       # INDEX_MAP/IDENTITY projected blocks
    task: TaskType
    re_configuration: GlmOptimizationConfiguration       # latent-factor solves
    matrix_configuration: GlmOptimizationConfiguration   # projection-matrix solve
    mf_configuration: MFOptimizationConfiguration
    base_offsets: np.ndarray
    # multi-chip: entity-axis sharding re-applied after every offset rebuild
    # (update_offsets produces host arrays — same contract as
    # RandomEffectCoordinate.mesh/_place)
    mesh: Optional[object] = None
    mesh_axes: Optional[tuple] = None
    # per-bucket SolverStats of the latent solves of the most recent update,
    # one per bucket and alternation (the CD driver sends them on as
    # SolverStatsEvents, as it does a random effect's)
    last_solver_stats: list = dataclasses.field(default_factory=list, repr=False)
    # base_offsets uploaded once for the device-plane updates
    _base_offsets_dev: Optional[jax.Array] = dataclasses.field(
        default=None, repr=False
    )

    supports_device_plane = True

    def __post_init__(self) -> None:
        # RANDOM-projected datasets carry no per-column global index map
        # (proj_indices are zeros), so B gathers/scatters would silently pile
        # onto row 0 — reject at construction.
        from photon_ml_tpu.projector import ProjectorType

        if self.dataset.config.projector is ProjectorType.RANDOM:
            raise ValueError(
                "FactoredRandomEffectCoordinate requires an INDEX_MAP or "
                "IDENTITY projected dataset (the factored coordinate learns "
                "its own projection matrix)"
            )

    def _init_matrix(self) -> jax.Array:
        """Gaussian random init scaled 1/sqrt(k) (reference seeds the
        factored problem with a random ProjectionMatrix, :95)."""
        k = self.mf_configuration.num_latent_factors
        rng = np.random.default_rng(self.mf_configuration.seed)
        B = rng.standard_normal((self.dataset.global_dim, k)) / np.sqrt(k)
        return jnp.asarray(B.astype(np.float32))

    def _place(self, ds: RandomEffectDataset) -> RandomEffectDataset:
        if self.mesh is None:
            return ds
        from photon_ml_tpu.data.random_effect import place_dataset

        return place_dataset(ds, self.mesh, self.mesh_axes)

    def update_model(
        self,
        model: Optional[FactoredRandomEffectModel],
        residual_scores: np.ndarray,
    ) -> FactoredRandomEffectModel:
        ds = self._place(
            self.dataset.update_offsets(self.base_offsets + residual_scores)
        )
        return self._alternate(ds, model)

    def update_model_device(
        self,
        model: Optional[FactoredRandomEffectModel],
        residual_scores: jax.Array,
    ) -> FactoredRandomEffectModel:
        """Device-plane update: base + residual offsets are regrouped into
        the entity blocks by the precomputed gather on the device
        (RandomEffectCoordinate.update_model_device)."""
        if self._base_offsets_dev is None:
            self._base_offsets_dev = jnp.asarray(
                np.asarray(self.base_offsets, dtype=np.float32)
            )
        ds = self._place(
            self.dataset.update_offsets_device(
                _fused_residual_offsets(self._base_offsets_dev, residual_scores)
            )
        )
        return self._alternate(ds, model)

    def _alternate(
        self,
        ds: RandomEffectDataset,
        model: Optional[FactoredRandomEffectModel],
    ) -> FactoredRandomEffectModel:
        """The alternating loop (:112-146) over a dataset that carries this
        update's offsets, warm-started from ``model``."""
        mf = self.mf_configuration
        B = model.projection_matrix if model is not None else self._init_matrix()
        latent_model = model.latent if model is not None else None
        stats: list = []
        with span(
            "mf/update",
            alternations=mf.num_iterations,
            latent_factors=mf.num_latent_factors,
        ):
            for alternation in range(mf.num_iterations):
                # (a) per-entity latent solve in the space of the current B
                with span("mf/project", device_sync=True, alternation=alternation):
                    latent_ds = _latent_dataset(ds, B)
                with span(
                    "mf/solve_latent",
                    alternation=alternation,
                    buckets=len(ds.buckets),
                ):
                    latent_model, _ = train_random_effects(
                        latent_ds,
                        self.task,
                        self.re_configuration,
                        initial_model=latent_model,
                        stats_out=stats,
                    )
                # (b) global projection-matrix solve over implicit kron features
                B = self._solve_matrix(ds, latent_model, B, alternation)
        self.last_solver_stats = stats

        return FactoredRandomEffectModel(
            random_effect_type=self.dataset.config.random_effect_type,
            task=self.task,
            latent=latent_model,
            projection_matrix=B,
        )

    def _solve_matrix(
        self,
        ds: RandomEffectDataset,
        latent_model: RandomEffectModel,
        B: jax.Array,
        alternation: int = 0,
    ) -> jax.Array:
        cfg = self.matrix_configuration
        use_l1 = cfg.l1_weight > 0
        solver = _matrix_solve_program(self.task, cfg.optimizer_config, use_l1)
        # not a ``glm/solve`` span: those are the sparse fixed-effect solves,
        # and what reads them counts two maps over that matrix an evaluation
        with span(
            "mf/solve_matrix", device_sync=True, alternation=alternation
        ) as solving:
            result = solver(
                B,
                [b.X for b in ds.buckets],
                [b.proj_indices for b in ds.buckets],
                list(latent_model.coefficients),
                [b.labels for b in ds.buckets],
                [b.offsets for b in ds.buckets],
                [b.weights for b in ds.buckets],
                jnp.float32(cfg.l2_weight),
                jnp.float32(cfg.l1_weight),
            )
            if get_tracer().enabled:
                # a traced run waits for the solve here, so that the span
                # holds the device's work and can say what it counted
                jax.block_until_ready(result)
                solving.set_attrs(
                    iterations=int(result.iterations),
                    evaluations=int(result.evaluations),
                    coefficients=int(B.size),
                )
        return result.w.reshape(B.shape)

    def score(self, model: FactoredRandomEffectModel) -> np.ndarray:
        """Active + passive scores in original row order: the latent model
        scored over B-projected blocks (RandomEffectCoordinate.score
        semantics)."""
        latent_ds = _latent_dataset(self.dataset, model.projection_matrix)
        return score_random_effects(model.latent, latent_ds)

    def score_device(self, model: FactoredRandomEffectModel) -> jax.Array:
        """``score`` as a device-resident [num_rows] plane."""
        latent_ds = _latent_dataset(self.dataset, model.projection_matrix)
        return score_random_effects_device(model.latent, latent_ds)
