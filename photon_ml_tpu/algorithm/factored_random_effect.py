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
implicit [n, d·k] design matrix, so the existing L-BFGS/TRON solvers run
unchanged over vec(B). The maps run over an item-tiled slot layout
(:class:`KronTiles`), built once per coordinate from the buckets'
``proj_indices``: every real (entity, local column) slot sorted by its global
column and cut into tiles of ``TILE`` slots that all belong to one column.
An evaluation then reads each block once in a dense pass, moves one scalar a
slot between the two orders (a fixed permutation, routed once with the
layout and run by the routed maps' kernels, ``ops/fused_perm.py``), and
sums a tile's slots against the latents laid into tile order once per solve;
the [d, k] gradient takes one row a tile (about ``slots / TILE + d`` rows),
not one a slot.

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
from typing import Callable, List, NamedTuple, Optional

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
from photon_ml_tpu.ops import routing
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.ops.fused_perm import MIN_FUSED_SIZE, fused_permute, kernels_run
from photon_ml_tpu.ops.permute_net import DevicePlan, apply_plan, device_plan
from photon_ml_tpu.ops.sparse_perm import _build_plan_cached
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration, OptimizerConfig
from photon_ml_tpu.opt.solve import solve, solver_kind
from photon_ml_tpu.opt.state import SolveResult
from photon_ml_tpu.telemetry import note_jit_trace
from photon_ml_tpu.telemetry.span import get_tracer, span, upload
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


TILE = 128  # slots a tile: one lane row of the chip's vector unit


@struct.dataclass
class KronTiles:
    """The item-tiled slot layout of a coordinate's buckets (device arrays).

    A slot is one (entity, local column) of a bucket, numbered flat over the
    buckets' ``[E_b, D_b]`` in bucket order. The real slots (``proj_valid``)
    sorted by their global column and cut into tiles of ``TILE``: every tile
    belongs to one column, a column's last tile padded with dead positions,
    which name the entity one past the last (a zero latent).

    The maps move one value a slot between the two orders by a routed
    permutation of ``to_slots.size`` elements, both orders padded to it
    (:func:`slot_permutation`): ``to_slots`` takes the flat positions to
    slot order, ``to_positions`` (its inverse) slot order to the positions.
    A real slot and its position are paired; the dead slots, the dead
    positions and the padding are paired dead with dead."""

    entity_of: jax.Array      # [tiles, TILE] flat entity of each position
    item_of_tile: jax.Array   # [tiles] global column, ascending
    to_slots: DevicePlan      # positions -> slots
    to_positions: DevicePlan  # slots -> positions
    slots: int = struct.field(pytree_node=False)


class TileLayout(NamedTuple):
    """The layout on the host, as :func:`tile_layout` lays it."""

    slot_of: np.ndarray       # [positions] flat slot of each; dead: ``slots``
    entity_of: np.ndarray     # [positions] flat entity of each; dead: one past
    item_of_tile: np.ndarray  # [tiles] global column, ascending
    pos_of_slot: np.ndarray   # [slots] position of each; dead: ``positions``


def tile_layout(
    pidxs: List[np.ndarray], pvals: List[np.ndarray], tile: int = TILE
) -> TileLayout:
    """The layout of buckets with host ``proj_indices`` / ``proj_valid``
    (``[E_b, D_b]`` each). A stable sort
    keeps a column's slots in slot order, so the layout, and the order every
    map sums in, is a function of the buckets alone."""
    slots, items, entities = [], [], []
    slot_base = entity_base = 0
    for pidx, pval in zip(pidxs, pvals):
        e_n, d_n = pidx.shape
        e, d = np.nonzero(pval)
        slots.append(slot_base + e * d_n + d)
        items.append(pidx[e, d])
        entities.append(entity_base + e)
        slot_base += e_n * d_n
        entity_base += e_n
    order = np.argsort(np.concatenate(items), kind="stable")
    slots = np.concatenate(slots)[order]
    items = np.concatenate(items)[order]
    entities = np.concatenate(entities)[order]
    columns, first, counts = np.unique(items, return_index=True, return_counts=True)
    if columns.size == 0:  # no real slot: one dead tile
        columns, first, counts = np.zeros(1, np.int64), np.zeros(1, np.int64), np.zeros(1, np.int64)
    tiles_of = np.maximum(-(-counts // tile), 1)
    first_tile = np.cumsum(tiles_of) - tiles_of
    pos = (np.repeat(first_tile, counts) * tile
           + np.arange(items.size) - np.repeat(first, counts))
    n_pos = int(tiles_of.sum()) * tile
    slot_of = np.full(n_pos, slot_base, np.int32)
    slot_of[pos] = slots
    entity_of = np.full(n_pos, entity_base, np.int32)
    entity_of[pos] = entities
    pos_of_slot = np.full(slot_base, n_pos, np.int32)
    pos_of_slot[slots] = pos
    return TileLayout(
        slot_of=slot_of, entity_of=entity_of,
        item_of_tile=np.repeat(columns, tiles_of).astype(np.int32),
        pos_of_slot=pos_of_slot,
    )


def slot_permutation(layout: TileLayout) -> np.ndarray:
    """``perm`` with ``perm[s]`` the position slot ``s`` reads (the gather
    convention of ``routing.build_plan``), over ``S = routing.valid_size``
    of the larger count (at least one recursion level, which the fused
    kernels need). A real slot reads its position; every other index of
    ``[0, S)`` reads a position no real slot names, both in ascending order:
    the permutation, and so the plan cache's key, is a function of the
    layout alone."""
    n_slots, n_pos = layout.pos_of_slot.size, layout.slot_of.size
    S = routing.valid_size(max(n_slots, n_pos, MIN_FUSED_SIZE))
    real = np.flatnonzero(layout.pos_of_slot < n_pos)
    perm = np.empty(S, np.int64)
    perm[real] = layout.pos_of_slot[real]
    free_dst = np.ones(S, bool)
    free_dst[real] = False
    free_src = np.ones(S, bool)
    free_src[perm[real]] = False
    perm[free_dst] = np.flatnonzero(free_src)
    return perm


def build_kron_tiles(
    pidxs: List[np.ndarray], pvals: List[np.ndarray], tile: int = TILE
) -> KronTiles:
    """:func:`tile_layout` and its two routed plans, on the device. The
    plan is read from the plan cache where it holds one (span
    ``route/plan``, ``what="kron"``), its inverse made in one pass
    (``route/place``, ``what="kron"``)."""
    layout = tile_layout(pidxs, pvals, tile)
    plan = _build_plan_cached(slot_permutation(layout), None, what="kron")
    with span("route/place", what="kron", invert=routing.inverse_engine()):
        inverse = plan.invert()
    return KronTiles(
        **upload("kron", lambda: dict(
            entity_of=jnp.asarray(layout.entity_of.reshape(-1, tile)),
            item_of_tile=jnp.asarray(layout.item_of_tile),
            to_slots=device_plan(plan),
            to_positions=device_plan(inverse),
        )),
        slots=int(layout.pos_of_slot.size),
    )


def move_executor(arrays) -> str:
    """How the maps run their moves: ``"routed"``, the plan's fused
    kernels, where they run (a TPU, or the tests' interpreter) and no array
    of ``arrays`` is split over devices; else ``"xla"``, the same plan
    stage by stage in XLA, which GSPMD can partition and a Pallas call it
    cannot."""
    split = any(
        isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer)
        and len(a.sharding.device_set) > 1
        for a in arrays
    )
    return "routed" if kernels_run() and not split else "xla"


@struct.dataclass
class KronFeatures:
    """Implicit design matrix of the projection-matrix solve.

    Row (e, s) of bucket b has features kron(latent[e], x[e, s]) laid out as
    vec(B) with B of shape [d_global, k]: coefficient (c, j) multiplies
    x_value-at-global-col-c times latent[e, j]. Bucket blocks are carried as
    parallel lists; rows are the concatenation of all buckets' flattened
    [E*S] axes (padding rows have weight 0 upstream).

    The maps run over the item-tiled layout ``tiles``; ``vs`` holds the
    latents laid into it, ``[tiles, k, TILE]`` (zero at dead positions):
    make the features with :meth:`build`, once per solve. They are
    elementwise products and sums in float32 (no matmul, so no
    reduced-precision pass), in an order fixed by the layout, and moves of
    values by the layout's plans, run as ``moves`` says
    (:func:`move_executor`).
    """

    xs: List[jax.Array]        # per bucket [E, S, D] local features
    latents: List[jax.Array]   # per bucket [E, k]
    tiles: KronTiles
    vs: jax.Array              # [tiles, k, TILE]
    d_global: int = struct.field(pytree_node=False)
    k: int = struct.field(pytree_node=False)
    moves: str = struct.field(pytree_node=False)

    @classmethod
    def build(cls, xs, latents, tiles: KronTiles, d_global: int, k: int,
              moves: Optional[str] = None):
        """The maps over ``tiles``, the latents gathered into tile order here
        (they are constant for a solve: no evaluation gathers them again).
        ``moves`` defaults to :func:`move_executor` of ``xs``."""
        # one zero row after the entities: what a dead position reads
        v = jnp.concatenate(list(latents) + [jnp.zeros((1, k), latents[0].dtype)])
        vs = jnp.transpose(v[tiles.entity_of], (0, 2, 1))
        return cls(xs=xs, latents=latents, tiles=tiles, vs=vs, d_global=d_global,
                   k=k, moves=moves or move_executor(xs))

    @property
    def num_rows(self) -> int:
        return sum(x.shape[0] * x.shape[1] for x in self.xs)

    @property
    def dim(self) -> int:
        return self.d_global * self.k

    def matvec(self, w: jax.Array) -> jax.Array:
        B = w.reshape(self.d_global, self.k)
        # u[slot] = B[item] . v[entity], a tile at a time: one row of B a tile
        u_tiles = jnp.sum(self.vs * B[self.tiles.item_of_tile][:, :, None], axis=1)
        # a dead position holds 0 (its latent is the zero row), and so does
        # every dead slot, which reads one
        u_all = self._to_slots(u_tiles.reshape(-1))
        outs, start = [], 0
        for x in self.xs:
            e_n, _, d_n = x.shape
            u = u_all[start : start + e_n * d_n].reshape(e_n, d_n)
            start += e_n * d_n
            outs.append(jnp.sum(x * u[:, None, :], axis=-1).reshape(-1))
        return jnp.concatenate(outs)

    def rmatvec(self, c: jax.Array) -> jax.Array:
        return self._rmatvec(c, squared=False)

    def rmatvec_sq(self, c: jax.Array) -> jax.Array:
        return self._rmatvec(c, squared=True)

    def _rmatvec(self, c: jax.Array, squared: bool) -> jax.Array:
        # a[slot] = sum_s c[e, s] x[e, s, d]: one dense pass over each block
        parts, start = [], 0
        for x in self.xs:
            e_n, s_n, _ = x.shape
            cb = c[start : start + e_n * s_n].reshape(e_n, s_n)
            start += e_n * s_n
            parts.append(jnp.sum(cb[:, :, None] * (x * x if squared else x), axis=1))
        # a dead slot holds 0 (x is 0 at a local column that is not real),
        # and so does every dead position, which reads one
        a_tiles = self._to_positions(jnp.concatenate([p.reshape(-1) for p in parts]))
        vs = self.vs * self.vs if squared else self.vs
        per_tile = jnp.sum(vs * a_tiles[:, None, :], axis=-1)  # [tiles, k]
        grad = jax.ops.segment_sum(
            per_tile, self.tiles.item_of_tile, num_segments=self.d_global,
            indices_are_sorted=True,
        )
        return grad.reshape(-1)

    def _to_slots(self, u: jax.Array) -> jax.Array:
        """``[positions]`` values in slot order, ``[slots]``."""
        return self._permute(self.tiles.to_slots, u)[: self.tiles.slots]

    def _to_positions(self, a: jax.Array) -> jax.Array:
        """``[slots]`` values in tile order, ``[tiles, TILE]``."""
        shape = self.tiles.entity_of.shape
        return self._permute(self.tiles.to_positions, a)[: shape[0] * shape[1]].reshape(shape)

    def _permute(self, plan: DevicePlan, flat: jax.Array) -> jax.Array:
        """``flat`` padded with zeros to the plan's size and moved by it:
        exact, a permutation adds nothing."""
        x = jnp.pad(flat, (0, plan.size - flat.shape[0]))
        if self.moves == "routed":
            return fused_permute(plan, x)
        return apply_plan(plan, x, kernels=False)

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
    task: TaskType, optimizer_config: OptimizerConfig, use_l1: bool, moves: str
) -> Callable[..., SolveResult]:
    """The jitted step (b) of one static key: ``(B0, xs, latents, labels,
    offsets, weights, l2, l1, tiles) -> SolveResult`` over vec(B), the
    per-bucket blocks as lists and ``tiles`` the coordinate's
    :class:`KronTiles`, its moves run as ``moves`` says
    (:func:`move_executor`). Kept for the process in this cache of its own: ``train_glm``'s ``_solve_program`` keeps ONE program (a kept
    program keeps its device reservation), and in a GAME fit that one is the
    fixed effect's — a matrix solve routed through it would evict it at
    every update. Everything that varies between calls is an argument, the
    regularization weights among them, so the key holds the task and the
    optimizer's settings."""
    objective = make_glm_objective(loss_for_task(task))
    configuration = GlmOptimizationConfiguration(optimizer_config=optimizer_config)
    kind = solver_kind(configuration, 1.0 if use_l1 else 0.0)

    def mf_matrix_solve(B0, xs, latents, labels, offsets, weights, l2, l1, tiles):
        note_jit_trace("mf_matrix_solve", kind)  # fires only on a (re)trace
        d_global, k = B0.shape
        feats = KronFeatures.build(xs, latents, tiles, d_global, k, moves)
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
    # the item-tiled slot layout of the matrix solve, built at the first solve
    _kron_tiles: Optional[KronTiles] = dataclasses.field(
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
        xs = [b.X for b in ds.buckets]
        latents = list(latent_model.coefficients)
        moves = move_executor(xs + latents)
        solver = _matrix_solve_program(self.task, cfg.optimizer_config, use_l1, moves)
        tiles = self._layout(ds)
        # not a ``glm/solve`` span: those are the sparse fixed-effect solves,
        # and what reads them counts two maps over that matrix an evaluation
        with span(
            "mf/solve_matrix", device_sync=True, alternation=alternation,
            moves=moves, move_slots=tiles.to_slots.size,
        ) as solving:
            result = solver(
                B,
                xs,
                latents,
                [b.labels for b in ds.buckets],
                [b.offsets for b in ds.buckets],
                [b.weights for b in ds.buckets],
                jnp.float32(cfg.l2_weight),
                jnp.float32(cfg.l1_weight),
                tiles,
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

    def _layout(self, ds: RandomEffectDataset) -> KronTiles:
        """The matrix solve's item-tiled layout of ``ds``'s buckets and its
        plans, built from their index maps at the first solve and kept: an
        update's buckets change only in their offsets, never in their
        slots."""
        slots = sum(int(np.prod(b.proj_indices.shape)) for b in ds.buckets)
        if self._kron_tiles is None:
            self._kron_tiles = build_kron_tiles(
                [np.asarray(b.proj_indices) for b in ds.buckets],
                [np.asarray(b.proj_valid) for b in ds.buckets],
            )
        elif self._kron_tiles.slots != slots:
            raise ValueError(
                f"the buckets hold {slots} slots and the kept layout "
                f"{self._kron_tiles.slots}"
            )
        return self._kron_tiles

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
