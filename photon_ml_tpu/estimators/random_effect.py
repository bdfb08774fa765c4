"""Random-effect training and scoring: vmap'd local solves.

Reference parity: algorithm/RandomEffectCoordinate.scala:39 — updateModel
(:103-143) runs ``activeData.join(problems).join(models).mapValues{ local
Breeze solve }``, i.e. millions of independent optimizations inside executor
closures; score (:157-187) covers active + passive data. Here each dataset
bucket becomes ONE jit-compiled program: ``vmap(solver)`` over the entity
axis — every entity's full L-BFGS/TRON/OWL-QN while_loop runs in lockstep
lanes on the MXU with zero cross-entity communication. Sharding the entity
axis over a mesh scales this to a pod with no collectives in the solve.

Convergence-adaptive driver: a lockstep dispatch runs until its SLOWEST
entity converges, so on skewed workloads most lanes burn dead iterations.
When ``configuration.adaptive.enabled`` the per-bucket solve instead runs in
chunks of K outer iterations (full solver state — L-BFGS memory, OWL-QN
orthant state, TRON trust radius — carried across chunks, so the per-lane
trajectory is IDENTICAL to one-shot), pulls the converged mask after each
chunk, and dispatches the next chunk on the live lanes only. State and data
stay at the bucket's full width in entity order: a round hands the ONE chunk
program of the bucket shape an index vector (live lanes first) and a tile
count, and the program loops over that many tiles of ``T`` lanes (gather,
vmapped chunk, scatter back). The number of live lanes is an operand, never a
shape, so a bucket shape compiles one init, one chunk and one extract program
for the life of the process (``solver_trace_counts``).
"""

from __future__ import annotations

import collections
import functools
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec
import numpy as np

from photon_ml_tpu.parallel.mesh import fetch_global

from photon_ml_tpu.data.random_effect import RandomEffectDataset, ReBucket
from photon_ml_tpu.losses.objective import make_glm_objective
from photon_ml_tpu.losses.pointwise import loss_for_task
from photon_ml_tpu.models.random_effect import RandomEffectModel
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration
from photon_ml_tpu.opt.solve import (
    solve,
    solve_chunk,
    solve_finalize,
    solve_init,
    solver_kind,
)
from photon_ml_tpu.opt.state import SolveResult
from photon_ml_tpu.opt.tracking import SolverStats
from photon_ml_tpu.telemetry import note_jit_trace, span
from photon_ml_tpu.types import ConvergenceReason, TaskType

_NOT_CONVERGED = ConvergenceReason.NOT_CONVERGED.value

# Python-side jit-cache-miss counter: each key is (program, optimizer kind)
# and its count only grows when XLA actually (re)traces that program — the
# increment sits inside the traced body, which never executes on cache hits.
# Tests use this to assert one chunk program per bucket shape.
_TRACE_COUNTS: "collections.Counter[Tuple[str, str]]" = collections.Counter()


def solver_trace_counts() -> Dict[Tuple[str, str], int]:
    """Snapshot of the RE solver jit trace counters (testing/telemetry)."""
    return dict(_TRACE_COUNTS)


def _note_trace(program: str, kind: str) -> None:
    """Trace-time side effect shared by every RE program: the local
    counter tests assert on, plus the global telemetry jit.traces.*
    counter (telemetry.metrics.note_jit_trace)."""
    _TRACE_COUNTS[(program, kind)] += 1
    note_jit_trace(program, kind)


def _bucket_data(bucket: ReBucket) -> LabeledData:
    return LabeledData(
        features=DenseFeatures(matrix=bucket.X),
        labels=bucket.labels,
        offsets=bucket.offsets,
        weights=bucket.weights,
        norm=None,
    )


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


# A bucket is worked on in tiles of a sixteenth of its (power-of-two) width.
_TILES_PER_BUCKET = 16


def _tile_lanes(num_entities: int, min_lanes: int) -> int:
    """Lanes a tile of the adaptive chunk program holds: a function of the
    bucket's shape alone, so the host's bookkeeping and the traced program
    agree on it. ``min_lanes`` is its floor (a tile under that is launch
    overhead); a bucket of one tile is a lockstep dispatch."""
    return max(
        _next_pow2(min_lanes), _next_pow2(num_entities) // _TILES_PER_BUCKET
    )


def _is_multi_device(x) -> bool:
    sharding = getattr(x, "sharding", None)
    return sharding is not None and len(sharding.device_set) > 1


def _zero_start(bucket: ReBucket) -> jax.Array:
    """The zero model of one bucket, laid out as the bucket is: over a mesh
    the lanes are sharded as the solve hands them back, so that a solve from
    nothing and a warm-started one are one program."""
    shape = (bucket.num_entities, bucket.local_dim)
    sharding = getattr(bucket.X, "sharding", None)
    if _is_multi_device(bucket.X) and isinstance(sharding, NamedSharding):
        lanes = NamedSharding(sharding.mesh, PartitionSpec(sharding.spec[0], None))
        return jnp.zeros(shape, jnp.float32, device=lanes)
    return jnp.zeros(shape, dtype=jnp.float32)


class _RePrograms(NamedTuple):
    """Jitted programs for one (task, configuration, compute_variances)
    combination. jax.jit specializes each per input shape, and every shape
    is the bucket's: one program each per bucket shape — never per round,
    per step or per count of live lanes."""

    kind: str
    chunk_iters: int
    min_lanes: int
    oneshot: Callable    # (w0, data, pv, l2, l1) -> (SolveResult, w_masked, var|None)
    init: Callable       # (w0, data, l2, l1) -> batched solver state
    chunk: Callable      # (state, data, l2, live_idx, n_tiles) -> state with the
                         # lanes of the first n_tiles tiles advanced by <= K iters
    extract: Callable    # (state, data, pv, l2) -> (SolveResult, w_masked, var|None)


@functools.lru_cache(maxsize=None)
def _re_programs(
    task: TaskType,
    configuration: GlmOptimizationConfiguration,
    compute_variances: bool,
) -> _RePrograms:
    objective = make_glm_objective(loss_for_task(task))
    use_l1 = configuration.l1_weight > 0
    kind = solver_kind(configuration, None if use_l1 else 0.0)
    K = configuration.adaptive.chunk_iters
    min_lanes = configuration.adaptive.min_lanes

    def _mask_and_var(res: SolveResult, data, pv, l2):
        # padding columns have all-zero features; L2 keeps them at 0, but be
        # explicit so exported models never leak junk. Fused into the same
        # program as the solve/finalize so there is no separate dispatch.
        w = jnp.where(pv, res.w, 0.0)
        if compute_variances:
            diag = objective.hessian_diag(res.w, data, l2)
            var = jnp.where(pv, 1.0 / (diag + 1e-12), 0.0)
        else:
            var = None
        return w, var

    def oneshot_one(w0, data, pv, l2, l1):
        res = solve(
            objective, w0, data, configuration,
            l2_weight=l2, l1_weight=l1 if use_l1 else 0.0,
        )
        w, var = _mask_and_var(res, data, pv, l2)
        return res, w, var

    def init_one(w0, data, l2, l1):
        return solve_init(
            objective, w0, data, configuration,
            l2_weight=l2, l1_weight=l1 if use_l1 else 0.0,
        )

    def chunk_one(state, data, l2):
        return solve_chunk(
            objective, state, data, configuration, l2_weight=l2, num_iters=K
        )

    def extract_one(state, data, pv, l2):
        res = solve_finalize(state, configuration)
        w, var = _mask_and_var(res, data, pv, l2)
        return res, w, var

    def _oneshot(w0, data, pv, l2, l1):
        _note_trace("re_oneshot", kind)
        return jax.vmap(oneshot_one, in_axes=(0, 0, 0, None, None))(w0, data, pv, l2, l1)

    def _init(w0, data, l2, l1):
        _note_trace("re_init", kind)
        return jax.vmap(init_one, in_axes=(0, 0, None, None))(w0, data, l2, l1)

    def _chunk(state, data, l2, live_idx, n_tiles):
        _note_trace("re_chunk", kind)
        T = _tile_lanes(state.it.shape[0], min_lanes)

        def tile(t, state):
            idx = jax.lax.dynamic_slice_in_dim(live_idx, t * T, T)
            lanes, lane_data = jax.tree.map(lambda a: a[idx], (state, data))
            lanes = jax.vmap(chunk_one, in_axes=(0, 0, None))(lanes, lane_data, l2)
            return jax.tree.map(lambda a, new: a.at[idx].set(new), state, lanes)

        return jax.lax.fori_loop(0, n_tiles, tile, state)

    def _extract(state, data, pv, l2):
        _note_trace("re_extract", kind)
        return jax.vmap(extract_one, in_axes=(0, 0, 0, None))(state, data, pv, l2)

    # Donate the carried solver state so each round updates in place instead
    # of copying the (w, memory, history) buffers.
    return _RePrograms(
        kind=kind,
        chunk_iters=K,
        min_lanes=min_lanes,
        oneshot=jax.jit(_oneshot),
        init=jax.jit(_init),
        chunk=jax.jit(_chunk, donate_argnums=(0,)),
        extract=jax.jit(_extract),
    )


def _solve_bucket_adaptive(
    progs: _RePrograms,
    bucket: ReBucket,
    w0: jax.Array,
    l2: jax.Array,
    l1: jax.Array,
    max_iterations: int,
    bucket_index: int,
):
    """Chunked rounds over the live lanes of one bucket. Returns
    (SolveResult, masked w, variances|None, SolverStats); the first three
    stay on the device, in entity order."""
    E = bucket.num_entities
    K = progs.chunk_iters
    T = _tile_lanes(E, progs.min_lanes)
    data = _bucket_data(bucket)
    retrace0 = _TRACE_COUNTS[("re_chunk", progs.kind)]

    state = progs.init(w0, data, l2, l1)
    # live lanes first, each group in entity order; round 0 takes every lane
    # (a lane converged at init never advances, and no mask is pulled for it)
    order = np.arange(E, dtype=np.int32)
    n_tiles = -(-E // T)
    pad = n_tiles * T - E
    its = np.zeros(E, dtype=np.int64)
    executed = 0
    widths: List[int] = []
    # ceil(max_iter/K) chunks always finish every lane; +1 slack for the
    # converged-at-init case where the first chunk advances nothing.
    max_rounds = -(-max_iterations // K) + 1

    for round_index in range(max_rounds):
        # the slots past E repeat the last lane in the order: a done lane
        # once any is done, else lane E-1, which shares their tile; either
        # way every copy computes what its original does
        live_idx = np.concatenate([order, np.full(pad, order[-1], np.int32)])
        with span(
            "re/adaptive_round",
            bucket=bucket_index,
            round=round_index,
            width=n_tiles * T,
            tiles=n_tiles,
        ):
            state = progs.chunk(state, data, l2, live_idx, np.int32(n_tiles))
            widths.append(n_tiles * T)
            its_before = its
            # the host's wait for the device, once a round: the two pulls
            # block until the chunk just dispatched has retired
            with span("re/round_wait"):
                its = np.asarray(jax.device_get(state.it)).astype(np.int64)
                reasons = np.asarray(jax.device_get(state.reason))
        # a tile's while_loop runs until its slowest lane stops
        advance = (its - its_before)[live_idx[: n_tiles * T]]
        executed += T * int(advance.reshape(n_tiles, T).max(axis=1).sum())
        done = (reasons != _NOT_CONVERGED) | (its >= max_iterations)
        n_live = E - int(np.sum(done))
        if n_live == 0:
            break
        order = np.argsort(done, kind="stable").astype(np.int32)
        n_tiles = -(-n_live // T)

    res, w, var = progs.extract(state, data, bucket.proj_valid, l2)
    reasons = np.asarray(jax.device_get(res.reason))
    max_its = int(its.max())  # E > min_lanes >= 1 lanes: never empty
    stats = SolverStats(
        bucket=bucket_index,
        optimizer=progs.kind,
        num_entities=E,
        rounds=len(widths),
        chunk_iters=K,
        dispatch_widths=tuple(widths),
        iterations_p50=float(np.percentile(its, 50)),
        iterations_p99=float(np.percentile(its, 99)),
        iterations_max=max_its,
        sum_entity_iterations=int(its.sum()),
        executed_lane_iterations=int(executed),
        lockstep_lane_iterations=E * max_its,
        converged=int(np.sum(reasons != _NOT_CONVERGED)),
        chunk_retraces=_TRACE_COUNTS[("re_chunk", progs.kind)] - retrace0,
    )
    return res, w, var, stats


def _solve_bucket_oneshot(
    progs: _RePrograms,
    bucket: ReBucket,
    w0: jax.Array,
    l2: jax.Array,
    l1: jax.Array,
    bucket_index: int,
):
    """Classic lockstep dispatch (adaptive disabled / sharded / tiny bucket);
    masking and variances run inside the same jit program."""
    data = _bucket_data(bucket)
    res, w, var = progs.oneshot(w0, data, bucket.proj_valid, l2, l1)
    E = bucket.num_entities
    its = np.asarray(fetch_global(res.iterations)).astype(np.int64)
    reasons = np.asarray(fetch_global(res.reason))
    max_its = int(its.max()) if its.size else 0
    stats = SolverStats(
        bucket=bucket_index,
        optimizer=progs.kind,
        num_entities=E,
        rounds=1,
        chunk_iters=progs.chunk_iters,
        dispatch_widths=(E,),
        iterations_p50=float(np.percentile(its, 50)) if its.size else 0.0,
        iterations_p99=float(np.percentile(its, 99)) if its.size else 0.0,
        iterations_max=max_its,
        sum_entity_iterations=int(its.sum()),
        executed_lane_iterations=E * max_its,
        lockstep_lane_iterations=E * max_its,
        converged=int(np.sum(reasons != _NOT_CONVERGED)),
        chunk_retraces=0,
    )
    return res, w, var, stats


def train_random_effects(
    dataset: RandomEffectDataset,
    task: TaskType,
    configuration: GlmOptimizationConfiguration,
    initial_model: Optional[RandomEffectModel] = None,
    compute_variances: bool = False,
    stats_out: Optional[List[SolverStats]] = None,
    overlap_buckets: int = 0,
) -> tuple[RandomEffectModel, List[SolveResult]]:
    """Solve one GLM per entity (all buckets). Returns the model and the
    per-bucket vmap'd SolveResults (per-entity convergence telemetry — the
    RandomEffectOptimizationTracker equivalent).

    When ``configuration.adaptive.enabled`` each bucket runs through the
    convergence-adaptive driver (chunked rounds over tiles of the live
    lanes); sharded buckets and buckets at/below ``adaptive.min_lanes`` fall back to
    the one-shot lockstep dispatch, whose results are identical. If
    ``stats_out`` is given, one :class:`SolverStats` per bucket is appended.

    ``overlap_buckets >= 2`` overlaps that many bucket solves on worker
    threads (the async CD schedule's RE leg): while one bucket's adaptive
    driver blocks on its converged-mask pull or orders the live lanes on
    the host, another bucket's chunk dispatches keep the device busy.
    Bucket solves are mutually independent and the programs come from the
    same per-shape registry, so per-bucket results are
    bitwise-identical to the sequential path and no new retraces are
    introduced. Sharded (multi-device) buckets force the sequential path —
    collectives must be issued in one global order.
    """
    progs = _re_programs(task, configuration, compute_variances)
    adaptive = configuration.adaptive
    max_iter = configuration.optimizer_config.max_iterations

    l2 = jnp.float32(configuration.l2_weight)
    l1 = jnp.float32(configuration.l1_weight)

    def _warm_start(b, bucket):
        if initial_model is not None:
            return _fit_entity_axis(
                initial_model.coefficients[b], bucket.num_entities
            )
        return _zero_start(bucket)

    def _solve_one(b, bucket, w0, use_adaptive):
        if use_adaptive:
            return _solve_bucket_adaptive(
                progs, bucket, w0, l2, l1, max_iter, b
            )
        return _solve_bucket_oneshot(progs, bucket, w0, l2, l1, b)

    use_adaptive_by_bucket = [
        adaptive.enabled
        and bucket.num_entities > adaptive.min_lanes
        and not _is_multi_device(bucket.X)
        for bucket in dataset.buckets
    ]
    overlap = (
        int(overlap_buckets) >= 2
        and len(dataset.buckets) > 1
        and not any(_is_multi_device(b.X) for b in dataset.buckets)
    )

    coeffs, variances, results = [], [], []
    if overlap:
        # lazy import: algorithm.coordinate imports this module at its top,
        # so a module-level import back into algorithm.* could deadlock the
        # partially-initialized package on first touch
        from photon_ml_tpu.algorithm.schedule import ScheduleExecutor

        solved = []
        with ScheduleExecutor(
            max_in_flight=min(int(overlap_buckets), len(dataset.buckets)),
            name="re-buckets",
        ) as executor:
            for b, bucket in enumerate(dataset.buckets):
                # warm-start layout on the driver; only the solve overlaps
                w0 = _warm_start(b, bucket)
                solved.append(
                    executor.submit(
                        b,
                        functools.partial(
                            _solve_one, b, bucket, w0, use_adaptive_by_bucket[b]
                        ),
                        span_name="re/solve_bucket",
                        bucket=b,
                        mode=(
                            "adaptive" if use_adaptive_by_bucket[b] else "oneshot"
                        ),
                        entities=bucket.num_entities,
                        optimizer=progs.kind,
                        overlap=True,
                    )
                )
            bucket_outs = [work.result() for work in solved]
        for res, w, var, stats in bucket_outs:
            coeffs.append(w)
            variances.append(var)
            results.append(res)
            if stats_out is not None:
                stats_out.append(stats)
    else:
        for b, bucket in enumerate(dataset.buckets):
            w0 = _warm_start(b, bucket)
            use_adaptive = use_adaptive_by_bucket[b]
            with span(
                "re/solve_bucket",
                device_sync=True,
                bucket=b,
                mode="adaptive" if use_adaptive else "oneshot",
                entities=bucket.num_entities,
                optimizer=progs.kind,
            ):
                res, w, var, stats = _solve_one(b, bucket, w0, use_adaptive)
            coeffs.append(w)
            variances.append(var)
            results.append(res)
            if stats_out is not None:
                stats_out.append(stats)

    model = RandomEffectModel(
        random_effect_type=dataset.config.random_effect_type,
        task=task,
        coefficients=coeffs,
        variances=variances,
        proj_indices=[b.proj_indices for b in dataset.buckets],
        proj_valid=[b.proj_valid for b in dataset.buckets],
        entity_ids=dataset.entity_ids,
        entity_to_loc=dataset.entity_to_loc,
        global_dim=dataset.global_dim,
        projector_type=dataset.config.projector,
        projection_seed=dataset.config.seed,
    )
    return model, results


def align_warm_start(
    model: RandomEffectModel, dataset: RandomEffectDataset
) -> RandomEffectModel:
    """Re-layout a trained RE model onto a DIFFERENT dataset's entity/bucket
    layout so it can warm-start ``train_random_effects`` there.

    ``train_random_effects`` consumes ``initial_model.coefficients[b]``
    positionally, which is only correct when the model was trained on the
    same dataset. The nearline path re-solves against a dataset built from a
    fresh events batch — different entities, different bucket packing,
    different local feature sets — so the old coefficients must be joined by
    entity id and re-scattered through the new dataset's projection indices.
    Entities the old model never saw start from zero (a fresh row).
    """
    from photon_ml_tpu.projector import ProjectorType

    if dataset.config.projector is ProjectorType.RANDOM:
        raise ValueError(
            "align_warm_start cannot re-scatter into a RANDOM-projected "
            "dataset: projected local spaces are seed/dim-dependent and "
            "global-space coefficients do not map back exactly"
        )
    coeffs = []
    for b, bucket in enumerate(dataset.buckets):
        idx_b = np.asarray(fetch_global(bucket.proj_indices))
        val_b = np.asarray(fetch_global(bucket.proj_valid))
        w = np.zeros(idx_b.shape, dtype=np.float32)
        for e, eid in enumerate(dataset.entity_ids[b]):
            old = model.coefficients_for(eid)
            if not old:
                continue
            row_idx, row_ok = idx_b[e], val_b[e]
            for j in range(len(row_idx)):
                if row_ok[j]:
                    w[e, j] = old.get(int(row_idx[j]), 0.0)
        coeffs.append(jnp.asarray(w))
    return RandomEffectModel(
        random_effect_type=dataset.config.random_effect_type,
        task=model.task,
        coefficients=coeffs,
        variances=[None] * len(coeffs),
        proj_indices=[b.proj_indices for b in dataset.buckets],
        proj_valid=[b.proj_valid for b in dataset.buckets],
        entity_ids=dataset.entity_ids,
        entity_to_loc=dataset.entity_to_loc,
        global_dim=dataset.global_dim,
        projector_type=dataset.config.projector,
        projection_seed=dataset.config.seed,
    )


@jax.jit
def _score_bucket(w: jax.Array, bucket: ReBucket) -> jax.Array:
    return jnp.einsum("esd,ed->es", bucket.X, w)


@jax.jit
def _score_passive(w: jax.Array, X: jax.Array, entity_index: jax.Array) -> jax.Array:
    return jnp.einsum("pd,pd->p", X, w[entity_index])


def _fit_entity_axis(w: jax.Array, num_entities: int) -> jax.Array:
    """Adapt a per-bucket coefficient block to the dataset's entity axis.

    Mesh padding grows the entity axis with trivial lanes; a model trained
    on a padded dataset carries the extra zero rows, a model from an
    unpadded (or differently-padded) run does not. Real entities always
    occupy the leading rows in build order, so pad with zeros / trim to
    align (reference analog: RandomEffectModel joins by REId and tolerates
    missing entities, RandomEffectModel.scala:~150).
    """
    e = w.shape[0]
    if e == num_entities:
        return w
    if e < num_entities:
        return jnp.pad(w, [(0, num_entities - e)] + [(0, 0)] * (w.ndim - 1))
    return w[:num_entities]


@jax.jit
def _gathered_scores(coeffs, buckets, passives, row_gather):
    """All buckets' active + passive scores assembled into the row-order
    plane with one gather through the precomputed row -> source-slot index
    (``RandomEffectDataset.row_gather``). XLA scatter-add serializes on CPU
    (and degrades on TPU); every row has exactly one source slot, so the
    gather is its fast dual and reproduces the scatter bitwise — padding
    slots and inactive lanes are simply never referenced."""
    parts = []
    for w, bucket, p in zip(coeffs, buckets, passives):
        w_b = _fit_entity_axis(w, bucket.num_entities)
        parts.append(jnp.einsum("esd,ed->es", bucket.X, w_b).reshape(-1))
        if p is not None:
            parts.append(jnp.einsum("pd,pd->p", p.X, w[p.entity_index]))
    flat = jnp.concatenate(parts + [jnp.zeros(1, dtype=jnp.float32)])
    return flat[row_gather]


def score_random_effects_device(
    model: RandomEffectModel, dataset: RandomEffectDataset
) -> jax.Array:
    """Device-plane :func:`score_random_effects`: the same active + passive
    scores, assembled into a device-resident [num_rows] plane — no host
    round trip. Numerically identical to the host path (each row has
    exactly one source bucket/slot)."""
    return _gathered_scores(
        list(model.coefficients),
        dataset.buckets,
        dataset.passive,
        dataset.gather_index(),
    )


def score_random_effects(
    model: RandomEffectModel, dataset: RandomEffectDataset
) -> np.ndarray:
    """Raw per-row scores x . w_entity aligned with the ORIGINAL row order
    (active + passive rows; reference RandomEffectCoordinate.score
    :157-187 = active join + passive broadcast scoring). Offsets are NOT
    included — score algebra composes them at the coordinate level."""
    out = np.zeros(dataset.num_rows, dtype=np.float32)
    for b, bucket in enumerate(dataset.buckets):
        w_b = _fit_entity_axis(model.coefficients[b], bucket.num_entities)
        z = fetch_global(_score_bucket(w_b, bucket))
        wt = fetch_global(bucket.weights)
        pos = fetch_global(bucket.sample_pos)
        mask = wt > 0
        out[pos[mask]] = z[mask]
        p = dataset.passive[b]
        if p is not None:
            zp = fetch_global(
                _score_passive(model.coefficients[b], p.X, p.entity_index)
            )
            out[np.asarray(p.sample_pos)] = zp
    return out
