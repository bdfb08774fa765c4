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
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from photon_ml_tpu.algorithm.coordinate import Coordinate
from photon_ml_tpu.data.random_effect import RandomEffectDataset, ReBucket
from photon_ml_tpu.estimators.random_effect import train_random_effects
from photon_ml_tpu.losses.objective import make_glm_objective
from photon_ml_tpu.losses.pointwise import loss_for_task
from photon_ml_tpu.models.random_effect import RandomEffectModel
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration
from photon_ml_tpu.opt.solve import solve
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

    k = int(B.shape[1])
    new_buckets = []
    new_passive = []
    for b, bucket in enumerate(dataset.buckets):
        Bg = B[bucket.proj_indices]  # [E, D, k]; padding cols have x == 0
        Xl = jnp.einsum("esd,edk->esk", bucket.X, Bg)
        e_n = bucket.num_entities
        new_buckets.append(
            bucket.replace(
                X=Xl,
                proj_indices=jnp.tile(jnp.arange(k, dtype=jnp.int32), (e_n, 1)),
                proj_valid=jnp.ones((e_n, k), dtype=bool),
            )
        )
        p = dataset.passive[b]
        if p is not None:
            Xp = jnp.einsum("pd,pdk->pk", p.X, Bg[p.entity_index])
            new_passive.append(p.replace(X=Xp))
        else:
            new_passive.append(None)
    return dataclasses.replace(
        dataset,
        buckets=new_buckets,
        passive=new_passive,
        global_dim=k,
        config=dataclasses.replace(
            dataset.config, projector=ProjectorType.IDENTITY, projected_dim=None
        ),
    )


@dataclasses.dataclass
class FactoredRandomEffectCoordinate(Coordinate):
    """Alternating MF-style coordinate (reference
    FactoredRandomEffectCoordinate.scala:40). It has no device-plane path:
    CoordinateDescent reaches it through Coordinate's host round trips."""

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
        B = model.projection_matrix if model is not None else self._init_matrix()
        latent_model = model.latent if model is not None else None

        for _ in range(self.mf_configuration.num_iterations):
            # (a) per-entity latent solve in the space of the current B
            latent_ds = _latent_dataset(ds, B)
            latent_model, _ = train_random_effects(
                latent_ds,
                self.task,
                self.re_configuration,
                initial_model=latent_model,
            )
            # (b) global projection-matrix solve over implicit kron features
            B = self._solve_matrix(ds, latent_model, B)

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
    ) -> jax.Array:
        feats = KronFeatures(
            xs=[b.X for b in ds.buckets],
            pidxs=[b.proj_indices for b in ds.buckets],
            latents=list(latent_model.coefficients),
            d_global=ds.global_dim,
            k=int(B.shape[1]),
        )
        labels = jnp.concatenate([b.labels.reshape(-1) for b in ds.buckets])
        offsets = jnp.concatenate([b.offsets.reshape(-1) for b in ds.buckets])
        weights = jnp.concatenate([b.weights.reshape(-1) for b in ds.buckets])
        data = LabeledData(
            features=feats, labels=labels, offsets=offsets, weights=weights, norm=None
        )
        objective = make_glm_objective(loss_for_task(self.task))
        result = solve(
            objective, B.reshape(-1), data, self.matrix_configuration
        )
        return result.w.reshape(B.shape)

    def score(self, model: FactoredRandomEffectModel) -> np.ndarray:
        """Active + passive scores in original row order: the latent model
        scored over B-projected blocks (RandomEffectCoordinate.score
        semantics)."""
        from photon_ml_tpu.estimators.random_effect import score_random_effects

        latent_ds = _latent_dataset(self.dataset, model.projection_matrix)
        return score_random_effects(model.latent, latent_ds)
