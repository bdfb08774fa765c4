"""Random-effect dataset: entity-grouped padded blocks for vmap'd solves.

Reference parity: data/RandomEffectDataSet.scala:47 (build :240-277 — groupBy
entity with a custom partitioner; active-data reservoir cap :287-388; passive
data :399-446; Pearson feature selection :457-471), data/LocalDataSet.scala:36
(per-entity in-memory dataset, feature selection :221-287, reservoir :289-320),
and projector/IndexMapProjectorRDD.scala:31 (per-entity index map built from
that entity's observed features :164).

TPU-native redesign: instead of an RDD of per-entity Scala objects, the whole
coordinate's data is a handful of dense padded blocks

    X [E, S, D_local]   labels/offsets/weights [E, S]   proj_indices [E, D_local]

where E = entities in a bucket, S = that bucket's max samples/entity, and
D_local = that bucket's max per-entity projected dimension. Entities are
size-bucketed so padding waste stays bounded; one ``vmap`` of the local solver
per bucket replaces millions of ``mapValues`` closures. Per-entity index-map
projection (a sorted list of the entity's observed global feature ids) makes
local problems dense and small — the MXU-friendly layout — exactly the role
the reference's IndexMapProjector plays. Samples beyond the active cap form
the passive set: projected through the same per-entity map, score-only.

All grouping/projection runs host-side in vectorized numpy at data-prep time
(the analog of the reference's one-time shuffle), producing arrays that shard
over the mesh's entity axis with zero training-time communication.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from photon_ml_tpu.utils.nativesort import lexsort_pairs
from flax import struct

from photon_ml_tpu.projector import ProjectorType, RandomProjectionMatrix
from photon_ml_tpu.telemetry.span import span, upload


@dataclasses.dataclass(frozen=True)
class RandomEffectDataConfiguration:
    """Reference RandomEffectDataConfiguration.scala:42 (string mini-language
    ``reType,shard,numPartitions,activeCap,passiveLB,featureRatio,projector``
    with ``index_map``/``identity``/``random=k``) as a typed config.
    numPartitions is superseded by size-bucketing."""

    random_effect_type: str
    active_data_upper_bound: Optional[int] = None   # max active samples/entity
    passive_data_lower_bound: Optional[int] = None  # min samples for an entity to keep passive rows
    features_to_samples_ratio: Optional[float] = None  # cap D_local <= ratio * n_samples
    max_local_features: Optional[int] = None        # hard cap on D_local
    num_buckets: int = 1
    seed: int = 0
    # Projection of per-entity problems (reference ProjectorType):
    # INDEX_MAP (default, exact remap of observed features), IDENTITY
    # (local space == global space), RANDOM (shared Gaussian matrix,
    # ``projected_dim`` required — the `random=k` mini-language arm).
    projector: ProjectorType = ProjectorType.INDEX_MAP
    projected_dim: Optional[int] = None

    def __post_init__(self) -> None:
        if self.projector is ProjectorType.RANDOM:
            if not self.projected_dim:
                raise ValueError("RANDOM projector requires projected_dim (random=k)")
            if (
                self.features_to_samples_ratio is not None
                or self.max_local_features is not None
            ):
                raise ValueError(
                    "feature selection (features_to_samples_ratio / "
                    "max_local_features) does not apply to the RANDOM "
                    "projector; the projection itself bounds the local dim"
                )


@struct.dataclass
class ReBucket:
    """One size-bucket of entities, fully padded (device pytree)."""

    X: jax.Array             # [E, S, D] local-projected dense features
    labels: jax.Array        # [E, S]
    offsets: jax.Array       # [E, S]
    weights: jax.Array       # [E, S] (0 = padding)
    sample_pos: jax.Array    # [E, S] int32 original row index (0 where padding)
    proj_indices: jax.Array  # [E, D] int32 global feature id per local column
    proj_valid: jax.Array    # [E, D] bool: local column is a real feature

    @property
    def num_entities(self) -> int:
        return self.X.shape[0]

    @property
    def max_samples(self) -> int:
        return self.X.shape[1]

    @property
    def local_dim(self) -> int:
        return self.X.shape[2]


@struct.dataclass
class RePassiveRows:
    """Passive (score-only) rows of one bucket, local-projected. Offsets are
    not stored: passive scoring is the raw x.w gather; score algebra composes
    offsets at the coordinate level."""

    X: jax.Array            # [P, D]
    entity_index: jax.Array  # [P] int32 row into the bucket's entity axis
    sample_pos: jax.Array   # [P] int32 original row index


@dataclasses.dataclass
class RandomEffectDataset:
    """All buckets of one random-effect coordinate + host-side id maps."""

    config: RandomEffectDataConfiguration
    buckets: List[ReBucket]
    passive: List[Optional[RePassiveRows]]   # parallel to buckets
    entity_ids: List[List[str]]              # per bucket, per entity row
    entity_to_loc: Dict[str, Tuple[int, int]]  # id -> (bucket, row)
    num_rows: int                            # total rows in the source data
    global_dim: int
    # row -> slot in the concatenation of per-bucket flattened active score
    # blocks [E*S] (bucket order, each followed by its passive block [P]),
    # with one trailing zero slot for rows no bucket covers. The inverse of
    # the sample_pos scatter: scoring becomes a single gather, which stays
    # vectorized on backends (CPU, TPU) where scatter-add serializes.
    row_gather: Optional[jax.Array] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    @property
    def num_entities(self) -> int:
        return sum(len(ids) for ids in self.entity_ids)

    def to_summary_string(self) -> str:
        """Reference RandomEffectDataSet.toSummaryString
        (RandomEffectDataSet.scala:204-228): active/passive sample counts
        plus this layout's padding accounting. Device-side reductions only
        (collective-safe on sharded buckets — callers must invoke this
        symmetrically on every process, never behind per-process branches)."""
        import jax.numpy as jnp

        active = 0
        cells = 0
        for b in self.buckets:
            active += int(jnp.sum(b.weights > 0))
            cells += int(np.prod(b.weights.shape))
        passive = sum(
            0 if p is None else int(p.sample_pos.shape[0])
            for p in self.passive
        )
        pad = cells / active if active else float("nan")
        return (
            f"random-effect dataset '{self.config.random_effect_type}': "
            f"{self.num_entities} entities in {len(self.buckets)} buckets, "
            f"{active} active samples (padding {pad:.2f}x), "
            f"{passive} passive samples, global dim {self.global_dim}"
        )

    def update_offsets(self, offsets: np.ndarray) -> "RandomEffectDataset":
        """Rebuild the per-bucket offset blocks from a full-data offset vector
        (the residual trick: Coordinate.updateModel / addScoresToOffsets)."""
        from photon_ml_tpu.parallel.mesh import fetch_global

        offsets = np.asarray(offsets, dtype=np.float32)
        new_buckets = []
        for b in self.buckets:
            pos = fetch_global(b.sample_pos)
            wt = fetch_global(b.weights)
            off = np.where(wt > 0, offsets[pos], 0.0).astype(np.float32)
            new_buckets.append(b.replace(offsets=jnp.asarray(off)))
        return dataclasses.replace(self, buckets=new_buckets)

    def gather_index(self) -> jax.Array:
        """The cached ``row_gather`` permutation, built from host copies of
        the bucket layout on first use for datasets that were not produced by
        :func:`build_random_effect_dataset` (which precomputes it so the
        steady-state training loop never touches host memory)."""
        if self.row_gather is None:
            from photon_ml_tpu.parallel.mesh import fetch_global

            self.row_gather = jnp.asarray(_build_row_gather(
                self.num_rows,
                [
                    (fetch_global(b.sample_pos), fetch_global(b.weights))
                    for b in self.buckets
                ],
                [
                    None if p is None else np.asarray(fetch_global(p.sample_pos))
                    for p in self.passive
                ],
            ))
        return self.row_gather

    def update_offsets_device(self, offsets: jax.Array) -> "RandomEffectDataset":
        """Device-plane ``update_offsets``: regroup a full-data device offset
        vector into the entity-grouped [E, S] blocks with one jitted gather
        per bucket. ``sample_pos`` IS the precomputed row -> (bucket, lane,
        slot) permutation from build time, so no host rebuild happens — the
        whole regroup is a device gather masked by the active-slot mask."""
        new_buckets = [
            b.replace(
                offsets=_regroup_offsets(offsets, b.sample_pos, b.weights)
            )
            for b in self.buckets
        ]
        return dataclasses.replace(self, buckets=new_buckets)


def _build_row_gather(
    num_rows: int,
    actives: List[Tuple[np.ndarray, np.ndarray]],
    passive_pos: List[Optional[np.ndarray]],
) -> np.ndarray:
    """Invert the (sample_pos, weights>0) scatter into a row -> source-slot
    index over the concatenation [active_b0 | passive_b0 | active_b1 | ...]
    plus one trailing zero slot (rows outside every bucket gather 0.0).
    Active rows are unique across (bucket, lane, slot), so each row has
    exactly one source and the gather reproduces the scatter bitwise."""
    total = sum(pos.size for pos, _ in actives) + sum(
        0 if sp is None else sp.size for sp in passive_pos
    )
    inv = np.full(num_rows, total, dtype=np.int32)
    base = 0
    for (pos, wt), sp in zip(actives, passive_pos):
        flat_pos = np.asarray(pos).ravel()
        m = np.asarray(wt).ravel() > 0
        inv[flat_pos[m]] = (base + np.nonzero(m)[0]).astype(np.int32)
        base += flat_pos.size
        if sp is not None:
            inv[np.asarray(sp)] = (
                base + np.arange(sp.size, dtype=np.int32)
            )
            base += sp.size
    return inv


@jax.jit
def _regroup_offsets(
    offsets: jax.Array, sample_pos: jax.Array, weights: jax.Array
) -> jax.Array:
    """offsets[sample_pos] masked to active slots — the device-resident
    equivalent of the host rebuild in :meth:`RandomEffectDataset
    .update_offsets` (padding slots carry sample_pos 0; the mask keeps their
    offsets at exactly 0 like the host path)."""
    return jnp.where(weights > 0, offsets[sample_pos], 0.0)


def _expand_nnz(
    act_rows: np.ndarray, row_start: np.ndarray, row_end: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Flatten the CSR slices of ``act_rows`` into (sample_index, flat_index)
    pairs: sample_index points back into act_rows, flat_index into fc/fv."""
    cnt = row_end[act_rows] - row_start[act_rows]
    total = int(cnt.sum())
    rep = np.repeat(np.arange(len(act_rows), dtype=np.int64), cnt)
    within = np.arange(total, dtype=np.int64) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    return rep, row_start[act_rows][rep] + within


def _local_dense(
    act_rows: np.ndarray,
    local_cols: np.ndarray,
    row_start: np.ndarray,
    row_end: np.ndarray,
    fc: np.ndarray,
    fv: np.ndarray,
    out: np.ndarray,
) -> None:
    """Scatter the rows' features into ``out[sample, local_col]`` (features
    outside local_cols are dropped — index-map projection semantics)."""
    rep, fidx = _expand_nnz(act_rows, row_start, row_end)
    c, v = fc[fidx], fv[fidx]
    j = np.searchsorted(local_cols, c)
    j_clip = np.minimum(j, max(len(local_cols) - 1, 0))
    match = (j < len(local_cols)) & (local_cols[j_clip] == c) if len(local_cols) else np.zeros(len(c), dtype=bool)
    out[rep[match], j_clip[match]] = v[match]


def _pearson_scores_flat(
    ukeys: np.ndarray,
    ecol: np.ndarray,
    n_ent: int,
    nz_keys: np.ndarray,
    nz_v: np.ndarray,
    y_nz: np.ndarray,
    w_nz: np.ndarray,
    e_act: np.ndarray,
    y_act: np.ndarray,
    w_act: np.ndarray,
) -> np.ndarray:
    """|weighted Pearson| per (entity, local column), computed from segment
    sums over the nonzeros only — the vectorized equivalent of
    :func:`_pearson_scores` over every entity at once (zero feature values
    contribute nothing to the x-moments but their samples still weight the
    label moments, identical to the dense formula)."""
    W = np.bincount(e_act, weights=w_act, minlength=n_ent)
    W = np.maximum(W, 1e-12)
    my = np.bincount(e_act, weights=w_act * y_act, minlength=n_ent) / W
    vy = (
        np.bincount(e_act, weights=w_act * y_act * y_act, minlength=n_ent) / W
        - my * my
    )
    kidx = np.searchsorted(ukeys, nz_keys)
    m = len(ukeys)
    Sx = np.bincount(kidx, weights=w_nz * nz_v, minlength=m)
    Sxx = np.bincount(kidx, weights=w_nz * nz_v * nz_v, minlength=m)
    Sxy = np.bincount(kidx, weights=w_nz * nz_v * y_nz, minlength=m)
    We = W[ecol]
    mx = Sx / We
    cov = Sxy / We - mx * my[ecol]
    vx = Sxx / We - mx * mx
    denom = np.sqrt(np.maximum(vx * vy[ecol], 0.0))
    corr = np.where(denom > 1e-12, np.abs(cov) / np.maximum(denom, 1e-12), 0.0)
    const_nonzero = (vx <= 1e-12) & (np.abs(mx) > 0)
    return np.where(const_nonzero, np.inf, corr)


def _pearson_scores(x: np.ndarray, y: np.ndarray, w: np.ndarray) -> np.ndarray:
    """|Pearson correlation| of each local feature with the label over one
    entity's samples (reference LocalDataSet.scala:221-287). Constant features
    score 0 except an all-constant nonzero column (intercept-like) which the
    reference keeps — we emulate by scoring it +inf."""
    wsum = max(w.sum(), 1e-12)
    mx = (w[:, None] * x).sum(0) / wsum
    my = float((w * y).sum() / wsum)
    dx = x - mx
    dy = y - my
    cov = (w[:, None] * dx * dy[:, None]).sum(0) / wsum
    vx = (w[:, None] * dx * dx).sum(0) / wsum
    vy = float((w * dy * dy).sum() / wsum)
    denom = np.sqrt(np.maximum(vx * vy, 0.0))
    corr = np.where(denom > 1e-12, np.abs(cov) / np.maximum(denom, 1e-12), 0.0)
    # constant nonzero column (e.g. intercept): keep it (reference keeps
    # intercept during feature selection)
    const_nonzero = (vx <= 1e-12) & (np.abs(mx) > 0)
    return np.where(const_nonzero, np.inf, corr)


def _plan_buckets(samples: np.ndarray, dims: np.ndarray, nb: int) -> np.ndarray:
    """Entity → bucket assignment minimizing total padded cells.

    Exact DP over ≤512 candidate boundaries on entities sorted by
    (samples, dims): the cost of a bucket spanning sorted ranks (j, i] is
    count x maxS x maxD — the REAL padded-cell bill of one [E, maxS, maxD]
    block, with the two maxima tracked separately (a product surrogate can
    underestimate ~1000x when samples and dims anti-correlate). O(512² x
    nb) regardless of entity count (candidates are count-quantile
    collapsed, so boundaries are optimal at ~0.2% count granularity).
    The reference bounds the same skew with its partitioner + active cap
    (RandomEffectDataSet.scala:287-388); with dense padded blocks the
    bucket boundaries ARE the balancing mechanism, so they are optimized.
    """
    n = len(samples)
    if nb <= 1 or n <= 1:
        return np.zeros(n, dtype=np.int64)
    order = np.lexsort((dims, samples))
    s_sorted = samples[order].astype(np.float64)
    d_sorted = dims[order].astype(np.float64)
    m = min(512, n)
    bounds = np.unique((np.arange(1, m + 1, dtype=np.int64) * n) // m)  # prefix counts
    G = len(bounds)
    # group g covers sorted ranks [bounds[g-1], bounds[g]); sorted by
    # samples, so a range's maxS is its LAST group's max; maxD needs a
    # running max per range start
    starts = np.concatenate([[0], bounds[:-1]])
    grp_maxS = np.maximum.reduceat(s_sorted, starts)
    grp_maxD = np.maximum.reduceat(d_sorted, starts)
    # maxD[j, i-1] = max of groups j..i-1 (suffix cummax per row); an extra
    # all-zero row for j = G keeps the cand matrix rectangular (that column
    # is forbidden below anyway)
    maxD = np.zeros((G + 1, G))
    for j in range(G):
        maxD[j, j:] = np.maximum.accumulate(grp_maxD[j:])
    C = np.concatenate([[0], bounds]).astype(np.float64)  # [G+1] prefix counts

    # dp[j] = min cost of the first j candidate groups with at most k
    # buckets; splits[k][i-1] remembers the argmin boundary for backtrack
    dp = np.full(G + 1, np.inf)
    dp[0] = 0.0
    row = np.arange(G)[:, None]
    col = np.arange(G + 1)[None, :]
    forbid = col > row  # bucket (j, i] needs j <= i-1, i = row+1
    splits = []
    for _ in range(nb):
        # cand[i-1, j] = dp[j] + (C[i] - C[j]) * maxS(j,i] * maxD(j,i]
        cand = (
            dp[None, :]
            + (C[1:, None] - C[None, :]) * grp_maxS[:, None] * maxD.T
        )  # maxD.T is [G, G+1]: rows i-1, cols j (col G forbidden below)
        cand[forbid] = np.inf
        arg = np.argmin(cand, axis=1)                      # [G]
        best = cand[np.arange(G), arg]
        new_dp = np.concatenate([[0.0], np.minimum(best, dp[1:])])
        # keep the one-fewer-buckets solution where it is already better
        arg = np.where(best <= dp[1:], arg, -1)            # -1 = no new cut
        splits.append(arg)
        dp = new_dp

    # backtrack from the last group through the remembered argmins
    cuts = []
    i = G
    for k in range(len(splits) - 1, -1, -1):
        if i == 0:
            break
        j = int(splits[k][i - 1])
        if j < 0:
            continue  # this level added no bucket ending at i
        cuts.append((j, i))
        i = j
    assert i == 0, "bucket DP backtrack failed to reach the start"
    cuts.reverse()

    bucket_of = np.zeros(n, dtype=np.int64)
    for b, (j, i) in enumerate(cuts):
        lo, hi = int(C[j]), int(C[i])
        bucket_of[order[lo:hi]] = b
    return bucket_of


def build_random_effect_dataset(
    entity_ids: Sequence,
    feature_rows: np.ndarray,
    feature_cols: np.ndarray,
    feature_vals: np.ndarray,
    global_dim: int,
    labels: np.ndarray,
    config: RandomEffectDataConfiguration,
    offsets: Optional[np.ndarray] = None,
    weights: Optional[np.ndarray] = None,
) -> RandomEffectDataset:
    """Group rows by entity, cap/sample, project, bucket, and pad.

    entity_ids: per-row entity key (len n). feature_*: COO triplets over the
    global feature space. Rows with entities are ALL consumed: up to the active
    cap into solver blocks, the remainder into passive (score-only) rows.
    The host work is the span ``re/build_dataset`` (``entities``,
    ``buckets``); the buckets' upload after it is a ``data/upload``.
    """
    with span("re/build_dataset") as building:
        host = _pack_random_effect_dataset(
            entity_ids, feature_rows, feature_cols, feature_vals, global_dim,
            labels, config, offsets, weights,
        )
        building.set_attrs(entities=host.num_entities, buckets=len(host.buckets))
    buckets, passive, row_gather = upload("re_bucket", lambda: jax.tree.map(
        jnp.asarray, (host.buckets, host.passive, host.row_gather)
    ))
    return dataclasses.replace(
        host, buckets=buckets, passive=passive, row_gather=row_gather
    )


def _pack_random_effect_dataset(
    entity_ids, feature_rows, feature_cols, feature_vals, global_dim, labels,
    config, offsets, weights,
) -> RandomEffectDataset:
    """:func:`build_random_effect_dataset`'s work on the host: the dataset
    with numpy arrays where the device's will be."""
    n = len(entity_ids)
    labels = np.asarray(labels, dtype=np.float32)
    offsets = np.zeros(n, dtype=np.float32) if offsets is None else np.asarray(offsets, dtype=np.float32)
    weights = np.ones(n, dtype=np.float32) if weights is None else np.asarray(weights, dtype=np.float32)
    rng = np.random.default_rng(config.seed)

    # Entity codes: np.unique on the raw array (no per-row Python str()); the
    # string form is only materialized once per ENTITY for the id maps.
    ids_arr = np.asarray(entity_ids)
    uniq_raw, codes = np.unique(ids_arr, return_inverse=True)
    uniq = uniq_raw.astype(str)
    n_ent = len(uniq)
    counts = np.bincount(codes, minlength=n_ent)

    # CSR-ify the COO features once (row-sorted)
    feature_rows = np.asarray(feature_rows, dtype=np.int64)
    feature_cols = np.asarray(feature_cols, dtype=np.int64)
    feature_vals = np.asarray(feature_vals, dtype=np.float32)
    forder = lexsort_pairs(feature_rows)
    fr, fc, fv = feature_rows[forder], feature_cols[forder], feature_vals[forder]
    row_start = np.searchsorted(fr, np.arange(n))
    row_end = np.searchsorted(fr, np.arange(n) + 1)

    # ---- active/passive split, all entities at once -----------------------
    # Group rows by entity (random order within an entity when capping) and
    # keep the first `cap` per entity: a uniform without-replacement subset —
    # the vectorized equivalent of the reference's per-entity reservoir
    # (RandomEffectDataSet.scala:325-388).
    cap = config.active_data_upper_bound
    if cap is not None:
        perm = np.lexsort((rng.random(n), codes))
    else:
        perm = lexsort_pairs(codes)
    codes_p = codes[perm]
    ent_start_p = np.searchsorted(codes_p, np.arange(n_ent))
    rank_p = np.arange(n, dtype=np.int64) - ent_start_p[codes_p]
    if cap is not None:
        active_m = rank_p < cap
        lb = config.passive_data_lower_bound
        pas_m = ~active_m
        if lb is not None:
            pas_m &= counts[codes_p] >= lb
    else:
        active_m = np.ones(n, dtype=bool)
        pas_m = np.zeros(n, dtype=bool)
    act = perm[active_m]            # active rows, grouped by entity
    e_act_g = codes_p[active_m]     # entity code per active row
    s_act_g = rank_p[active_m]      # slot within entity
    pas = perm[pas_m]
    e_pas_g = codes_p[pas_m]
    acounts = np.bincount(e_act_g, minlength=n_ent)

    # Active nnz, expanded once (reused by projection + Pearson + scatter).
    rep_a, fidx_a = _expand_nnz(act, row_start, row_end)
    nz_e = e_act_g[rep_a]           # entity code per active nonzero
    nz_c = fc[fidx_a]
    nz_v = fv[fidx_a]

    rproj = (
        RandomProjectionMatrix(
            projected_dim=int(config.projected_dim),
            global_dim=int(global_dim),
            seed=config.seed,
        )
        if config.projector is ProjectorType.RANDOM
        else None
    )
    identity = config.projector is ProjectorType.IDENTITY
    G1 = global_dim + 1

    # ---- per-entity local column maps (INDEX_MAP), no entity loop ---------
    if rproj is not None or identity:
        ukeys = np.empty(0, dtype=np.int64)
        ecol = np.empty(0, dtype=np.int64)
        ucol = np.empty(0, dtype=np.int64)
        dlocs = (
            np.full(n_ent, global_dim, dtype=np.int64)
            if identity
            else np.zeros(n_ent, dtype=np.int64)
        )
    else:
        # observed (entity, col) pairs from ACTIVE data only (reference
        # IndexMapProjectorRDD.scala:164); np.unique returns them sorted by
        # entity then column — exactly the flat local-col layout.
        ukeys = np.unique(nz_e * G1 + nz_c)
        ecol = ukeys // G1
        ucol = ukeys % G1
        dlocs = np.bincount(ecol, minlength=n_ent)

        # feature-selection caps (ratio * samples, hard cap)
        d_cap_e = None
        if config.features_to_samples_ratio is not None:
            d_cap_e = np.maximum(
                (config.features_to_samples_ratio * acounts).astype(np.int64), 1
            )
        if config.max_local_features is not None:
            hard = int(config.max_local_features)
            d_cap_e = np.full(n_ent, hard, dtype=np.int64) if d_cap_e is None else np.minimum(d_cap_e, hard)
        if d_cap_e is not None and np.any(dlocs > d_cap_e):
            scores = _pearson_scores_flat(
                ukeys,
                ecol,
                n_ent,
                nz_keys=nz_e * G1 + nz_c,
                nz_v=nz_v,
                y_nz=labels[act][rep_a],
                w_nz=weights[act][rep_a],
                e_act=e_act_g,
                y_act=labels[act],
                w_act=weights[act],
            )
            # top-k per entity, stable on ties by column order (the flat
            # layout is column-sorted per entity, matching the reference's
            # stable argsort over local columns)
            sel = np.lexsort((np.arange(len(ukeys)), -scores, ecol))
            estart = np.searchsorted(ecol[sel], np.arange(n_ent))
            r2 = np.arange(len(ukeys), dtype=np.int64) - estart[ecol[sel]]
            kept = np.sort(sel[r2 < d_cap_e[ecol[sel]]])
            ukeys, ecol, ucol = ukeys[kept], ecol[kept], ucol[kept]
            dlocs = np.bincount(ecol, minlength=n_ent)

    dstart = np.zeros(n_ent + 1, dtype=np.int64)
    np.cumsum(dlocs, out=dstart[1:])

    # ---- size-bucketing by (samples x local dim) --------------------------
    # Split points are chosen by a small DP that MINIMIZES total padded
    # cells (sum over buckets of count x in-bucket max size): under a Zipf
    # entity-size tail, count-quantiles lump the giant head entities into a
    # bucket with thousands of medium ones (~3x padding measured) and
    # mass-quantiles stretch the tail bucket instead (~6x); the DP places
    # both kinds of boundary where they pay (tests/test_ragged_stress.py
    # gates the measured overhead at <2x).
    nb = max(1, min(config.num_buckets, n_ent))
    dims_e = (
        np.full(n_ent, rproj.projected_dim, dtype=np.int64)
        if rproj
        else np.maximum(dlocs, 1)
    )
    bucket_of = _plan_buckets(acounts, dims_e, nb)
    nb = int(bucket_of.max()) + 1 if n_ent else 1

    # Resolve every active nonzero's local column once (INDEX_MAP only).
    if rproj is None and not identity:
        qk = nz_e * G1 + nz_c
        ii = np.searchsorted(ukeys, qk)
        ii_c = np.minimum(ii, max(len(ukeys) - 1, 0))
        nz_match = (
            (ii < len(ukeys)) & (ukeys[ii_c] == qk)
            if len(ukeys)
            else np.zeros(len(qk), dtype=bool)
        )
        nz_j = ii_c - dstart[nz_e]  # local column per active nonzero
    elif identity:
        nz_match = np.ones(len(nz_c), dtype=bool)
        nz_j = nz_c

    def _project_rows(rows_g: np.ndarray) -> np.ndarray:
        """x_projected = B^T x per sample of ``rows_g`` (RANDOM projector)."""
        rep, fidx = _expand_nnz(rows_g, row_start, row_end)
        return rproj.project_coo(rep, fc[fidx], fv[fidx], len(rows_g))

    buckets: List[ReBucket] = []
    passives: List[Optional[RePassiveRows]] = []
    bucket_ids: List[List[str]] = []
    entity_to_loc: Dict[str, Tuple[int, int]] = {}
    host_actives: List[Tuple[np.ndarray, np.ndarray]] = []
    host_passive_pos: List[Optional[np.ndarray]] = []

    for b in range(nb):
        ent_m = bucket_of == b
        E = int(ent_m.sum())
        if E == 0:
            continue
        bi = len(buckets)
        # Cost-sorted dispatch: entity rows within the bucket are ordered by
        # DESCENDING active sample count (stable), so lockstep lanes carry
        # similar per-iteration work and the adaptive driver's compacted
        # prefixes keep heavy (slow-converging) entities co-scheduled.
        codes_b = np.nonzero(ent_m)[0]
        order_b = np.argsort(-acounts[codes_b], kind="stable")
        new_e = np.zeros(n_ent, dtype=np.int64)  # entity code -> row within bucket
        new_e[codes_b[order_b]] = np.arange(E, dtype=np.int64)
        S = int(acounts[ent_m].max())
        D = int(
            rproj.projected_dim
            if rproj
            else max(int(np.maximum(dlocs[ent_m], 1).max()), 1)
        )

        lab = np.zeros((E, S), dtype=np.float32)
        off = np.zeros((E, S), dtype=np.float32)
        wt = np.zeros((E, S), dtype=np.float32)
        pos = np.zeros((E, S), dtype=np.int32)
        rm = ent_m[e_act_g]
        er, sr = new_e[e_act_g[rm]], s_act_g[rm]
        lab[er, sr] = labels[act[rm]]
        off[er, sr] = offsets[act[rm]]
        wt[er, sr] = weights[act[rm]]
        pos[er, sr] = act[rm]

        pidx = np.zeros((E, D), dtype=np.int32)
        pval = np.zeros((E, D), dtype=bool)
        if rproj is not None:
            # projected-space coordinates are all live; back-projection goes
            # through the shared matrix, not pidx
            pval[:, :] = True
        elif identity:
            pidx[:, :] = np.arange(global_dim, dtype=np.int32)[None, :]
            pval[:, :] = True
        else:
            km = ent_m[ecol]
            jj = np.arange(len(ukeys), dtype=np.int64) - dstart[ecol]
            pidx[new_e[ecol[km]], jj[km]] = ucol[km]
            pval[new_e[ecol[km]], jj[km]] = True

        X = np.zeros((E, S, D), dtype=np.float32)
        if rproj is not None:
            X[er, sr] = _project_rows(act[rm])
        else:
            zm = ent_m[nz_e] & nz_match
            X[new_e[nz_e[zm]], s_act_g[rep_a[zm]], nz_j[zm]] = nz_v[zm]

        pm = ent_m[e_pas_g]
        pas_b = pas[pm]
        n_pas = len(pas_b)
        pX = np.zeros((n_pas, D), dtype=np.float32)
        if n_pas:
            if rproj is not None:
                pX = _project_rows(pas_b)
            else:
                rep_p, fidx_p = _expand_nnz(pas_b, row_start, row_end)
                pc, pv_ = fc[fidx_p], fv[fidx_p]
                pe = e_pas_g[pm][rep_p]
                if identity:
                    pX[rep_p, pc] = pv_
                else:
                    qk = pe * G1 + pc
                    ii = np.searchsorted(ukeys, qk)
                    ii_c = np.minimum(ii, max(len(ukeys) - 1, 0))
                    match = (
                        (ii < len(ukeys)) & (ukeys[ii_c] == qk)
                        if len(ukeys)
                        else np.zeros(len(qk), dtype=bool)
                    )
                    jcol = ii_c - dstart[pe]
                    pX[rep_p[match], jcol[match]] = pv_[match]

        ids_b = uniq[codes_b[order_b]].tolist()
        entity_to_loc.update(
            (eid, (bi, e)) for e, eid in enumerate(ids_b)
        )

        buckets.append(
            ReBucket(
                X=X, labels=lab, offsets=off, weights=wt, sample_pos=pos,
                proj_indices=pidx, proj_valid=pval,
            )
        )
        passives.append(
            RePassiveRows(
                X=pX,
                entity_index=new_e[e_pas_g[pm]].astype(np.int32),
                sample_pos=pas_b.astype(np.int32),
            )
            if n_pas
            else None
        )
        bucket_ids.append(ids_b)
        host_actives.append((pos, wt))
        host_passive_pos.append(
            pas_b.astype(np.int32) if n_pas else None
        )

    return RandomEffectDataset(
        config=config,
        buckets=buckets,
        passive=passives,
        entity_ids=bucket_ids,
        entity_to_loc=entity_to_loc,
        num_rows=n,
        global_dim=int(global_dim),
        row_gather=_build_row_gather(n, host_actives, host_passive_pos),
    )


def pad_entities_to_multiple(
    dataset: RandomEffectDataset, multiple: int
) -> RandomEffectDataset:
    """Pad every bucket's entity axis to a multiple (weight-0 entities with
    no real samples/features). Padded entity lanes carry no entity ids, so
    model extraction and scoring ignore them; padding once at build time
    keeps model/array shapes stable across coordinate-descent updates."""
    if multiple <= 1:
        return dataset
    new_buckets = []
    padded_any = False
    for b in dataset.buckets:
        pad = (-b.num_entities) % multiple
        if pad == 0:
            new_buckets.append(b)
            continue
        padded_any = True
        def pad0(a):
            return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        new_buckets.append(
            ReBucket(
                X=pad0(b.X),
                labels=pad0(b.labels),
                offsets=pad0(b.offsets),
                weights=pad0(b.weights),
                sample_pos=pad0(b.sample_pos),
                proj_indices=pad0(b.proj_indices),
                proj_valid=pad0(b.proj_valid),
            )
        )
    if not padded_any:
        return dataset
    # entity padding grows the flattened [E*S] blocks: the cached row_gather
    # slots shift, so drop it and let gather_index() rebuild lazily
    return dataclasses.replace(
        dataset, buckets=new_buckets, row_gather=None
    )


def place_dataset(dataset: RandomEffectDataset, mesh, axis_names) -> "RandomEffectDataset":
    """Shard every bucket's entity axis over the given mesh axes (replicated
    otherwise). Entity solves are independent, so this is pure data
    parallelism with zero collectives inside the vmap'd solver."""
    from jax.sharding import PartitionSpec as P

    from photon_ml_tpu.parallel.mesh import place

    def put(a):
        return place(a, mesh, P(axis_names, *([None] * (a.ndim - 1))))

    new_buckets = [jax.tree.map(put, b) for b in dataset.buckets]
    return dataclasses.replace(dataset, buckets=new_buckets)
