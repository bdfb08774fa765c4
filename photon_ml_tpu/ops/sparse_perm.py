"""Benes-routed sparse feature matrix: TPU-native large-d GLM compute.

The fixed-effect problem multiplies a huge sparse matrix (n rows, up to 1e9
columns, ~constant nnz/row) by dense vectors in both directions every
optimizer iteration (reference hot loop: ValueAndGradientAggregator
.scala:132-153). XLA's gather/scatter lower to ~10ns/element scalar loops on
TPU, so instead both directions are expressed with only dense vector
primitives and ONE static data movement:

- ``matvec`` (z = X w): broadcast w over the column-grouped (CSC-ELL) slot
  grid — a free relayout — then apply the inverse Benes permutation to land
  each w value at its row-grouped (ELL) slot, multiply by the stored values
  and row-sum. No gather.
- ``rmatvec`` (g = X^T c): broadcast c over ELL slots (free), apply the
  forward permutation to column-grouped slots, row-sum per column. The
  scatter-add became a padded segmented sum.

The permutation is routed once at prep time (ops/routing.py) and executed as
~2*log_128(S)-1 lane-shuffle passes (ops/permute_net.py). Cost per linear
map is a handful of full passes over the nnz arrays at HBM speed — the same
asymptotics as the reference's per-partition sparse axpy, but vectorized.

Layouts (S = routed network size, a padded power-of-128 multiple):

- ELL side: flat [S] position p = row * K + k for p < n*K (row-major slots,
  K = padded max nnz/row); positions >= n*K are dead padding.
- CSC side: flat [S] position q = col * KP + k' for q < d*KP (column-major
  slots, KP = padded max nnz/col); q >= d*KP dead.
- ``plan`` maps CSC position q -> ELL position p for real entries and pads
  to pads (a bijection on [0, S)); ``plan_inv`` is its inverse.
"""

from __future__ import annotations

from typing import Optional

import os

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from photon_ml_tpu.ops import routing
from photon_ml_tpu.telemetry.span import span, upload
from photon_ml_tpu.utils.nativesort import lexsort_pairs
from photon_ml_tpu.ops.permute_net import DevicePlan, apply_plan, device_plan


@struct.dataclass
class BenesSparseFeatures:
    """Sparse [n, d] feature matrix with Benes-routed linear maps.

    Drop-in sibling of ``ops.features.EllFeatures`` (same matvec/rmatvec/
    rmatvec_sq/row_norms_sq protocol) for the large-d fixed-effect path.

    High-degree ("hot") columns — intercept and frequent features, whose
    degree would otherwise set the CSC padding KP and blow up the routed
    network — are split out into a dense [n, H] side matrix that rides the
    MXU directly (z += X_hot @ w[hot_cols]; g[hot_cols] += X_hot^T c). The
    long tail stays in the permutation-routed sparse engine. The reference
    has no analog (Breeze sparse axpy is degree-oblivious); on TPU the
    split is what keeps both sides dense-regular.
    """

    ell_values: jax.Array     # [n, K] float32, 0 in padding slots
    csc_values: jax.Array     # [d, KP] float32, 0 in padding slots (= routed
                              # ell_values; stored to skip one permute)
    plan: DevicePlan          # CSC position q -> ELL position p
    plan_inv: DevicePlan      # ELL position p -> CSC position q
    hot_matrix: Optional[jax.Array]  # [n, H] dense hot columns (or None)
    hot_cols: Optional[jax.Array]    # [H] int32 original column ids
    num_rows_: int = struct.field(pytree_node=False)
    num_cols_: int = struct.field(pytree_node=False)
    # Spill side (KP cap, see plan_column_layout): entries beyond each
    # column's ``cap`` routed slots, evaluated by gather/scatter-add. The
    # auto planner prices each spilled entry at _spill_slot_cost() routed
    # slots and hard-bounds spill at max(nnz/8, 4096), so the scatter side
    # stays a small fraction of the network cost by construction.
    spill_rows: Optional[jax.Array] = None   # [M] int32
    spill_cols: Optional[jax.Array] = None   # [M] int32
    spill_vals: Optional[jax.Array] = None   # [M] float32

    @property
    def num_rows(self) -> int:
        return self.num_rows_

    @property
    def dim(self) -> int:
        return self.num_cols_

    @property
    def ell_k(self) -> int:
        return self.ell_values.shape[1]

    @property
    def csc_k(self) -> int:
        return self.csc_values.shape[1]

    def _to_ell(self, csc_flat: jax.Array) -> jax.Array:
        """Move a CSC-slot array into ELL slot order."""
        return apply_plan(self.plan_inv, csc_flat)

    def _to_csc(self, ell_flat: jax.Array) -> jax.Array:
        """Move an ELL-slot array into CSC slot order."""
        return apply_plan(self.plan, ell_flat)

    def _pad_ell(self, flat: jax.Array) -> jax.Array:
        return jnp.zeros(self.plan.size, flat.dtype).at[: flat.shape[0]].set(flat)

    def matvec(self, w: jax.Array) -> jax.Array:
        n, k = self.ell_values.shape
        d, kp = self.csc_values.shape
        wexp = jnp.broadcast_to(w[:, None], (d, kp)).reshape(-1)
        wexp = self._pad_ell(wexp) if wexp.shape[0] < self.plan.size else wexp
        w_ell = self._to_ell(wexp)[: n * k].reshape(n, k)
        z = jnp.sum(self.ell_values * w_ell, axis=-1)
        if self.hot_matrix is not None:
            z = z + self.hot_matrix @ w[self.hot_cols]
        if self.spill_rows is not None:
            z = z.at[self.spill_rows].add(self.spill_vals * w[self.spill_cols])
        return z

    def rmatvec(self, c: jax.Array) -> jax.Array:
        return self._rmatvec_impl(
            self.ell_values, self.hot_matrix, c, self.spill_vals
        )

    def rmatvec_sq(self, c: jax.Array) -> jax.Array:
        hot_sq = None if self.hot_matrix is None else self.hot_matrix * self.hot_matrix
        return self._rmatvec_impl(
            self.ell_values * self.ell_values, hot_sq, c,
            None if self.spill_vals is None
            else self.spill_vals * self.spill_vals,
        )

    def _rmatvec_impl(
        self,
        vals: jax.Array,
        hot: Optional[jax.Array],
        c: jax.Array,
        spill_vals: Optional[jax.Array] = None,
    ) -> jax.Array:
        n, k = vals.shape
        d, kp = self.csc_values.shape
        t = (vals * c[:, None]).reshape(-1)
        t = self._pad_ell(t) if t.shape[0] < self.plan.size else t
        t_csc = self._to_csc(t)[: d * kp].reshape(d, kp)
        g = jnp.sum(t_csc, axis=-1)
        if hot is not None:
            g = g.at[self.hot_cols].add(hot.T @ c)
        if spill_vals is not None:
            g = g.at[self.spill_cols].add(spill_vals * c[self.spill_rows])
        return g

    def row_norms_sq(self) -> jax.Array:
        sq = jnp.sum(self.ell_values * self.ell_values, axis=-1)
        if self.hot_matrix is not None:
            sq = sq + jnp.sum(self.hot_matrix * self.hot_matrix, axis=-1)
        if self.spill_rows is not None:
            sq = sq.at[self.spill_rows].add(self.spill_vals * self.spill_vals)
        return sq

    def to_dense(self):
        """Densify via one matvec per unit vector — test-scale only."""
        from photon_ml_tpu.ops.features import DenseFeatures

        eye = jnp.eye(self.num_cols_, dtype=self.ell_values.dtype)
        cols = jax.vmap(self.matvec, in_axes=1, out_axes=1)(eye)
        return DenseFeatures(matrix=cols)


@struct.dataclass
class ColumnSplitFeatures:
    """Sparse [n, d] matrix as independent column-block engines.

    The routed network's valid sizes step c*128^k with c in {1,2,4,8}
    (routing.valid_size), so a shard whose d*KP lands just past 8*128^k pays
    up to 16x slot padding (the 1B-coefficient layout's 2^24-column chip
    tile: d*KP = 2^26 rounds to 2^28). Splitting the column space into B
    blocks gives B networks of total size ~B * valid_size(d*KP/B) — back on
    the ladder — at the cost of B kernel dispatches per linear map inside
    one jit program. Every block is a full engine (own hot/spill sides);
    results are exact sums/concats of block results.
    """

    blocks: tuple                      # sub-engines (pytree node)
    # global hot-column dense side (ids in GLOBAL column space) — kept
    # outside the blocks so one [n, H] matmul serves the whole matrix
    hot_matrix: Optional[jax.Array]
    hot_cols: Optional[jax.Array]
    col_bounds: tuple = struct.field(pytree_node=False)  # len(blocks)+1 ints
    num_rows_: int = struct.field(pytree_node=False)
    num_cols_: int = struct.field(pytree_node=False)

    @property
    def num_rows(self) -> int:
        return self.num_rows_

    @property
    def dim(self) -> int:
        return self.num_cols_

    def _block_w(self, w: jax.Array, b: int) -> jax.Array:
        """w slice for block b, zero-padded to the block's width (pinned
        grid layouts give every block a uniform width that may overhang
        the true column count at the end)."""
        wb = w[self.col_bounds[b]: self.col_bounds[b + 1]]
        width = self.blocks[b].dim
        if wb.shape[0] < width:
            wb = jnp.pad(wb, (0, width - wb.shape[0]))
        return wb

    def matvec(self, w: jax.Array) -> jax.Array:
        z = None
        for b, blk in enumerate(self.blocks):
            zb = blk.matvec(self._block_w(w, b))
            z = zb if z is None else z + zb
        if self.hot_matrix is not None:
            z = z + self.hot_matrix @ w[self.hot_cols]
        return z

    def rmatvec(self, c: jax.Array) -> jax.Array:
        g = jnp.concatenate(
            [blk.rmatvec(c) for blk in self.blocks]
        )[: self.num_cols_]
        if self.hot_matrix is not None:
            g = g.at[self.hot_cols].add(self.hot_matrix.T @ c)
        return g

    def rmatvec_sq(self, c: jax.Array) -> jax.Array:
        g = jnp.concatenate(
            [blk.rmatvec_sq(c) for blk in self.blocks]
        )[: self.num_cols_]
        if self.hot_matrix is not None:
            hm2 = self.hot_matrix * self.hot_matrix
            g = g.at[self.hot_cols].add(hm2.T @ c)
        return g

    def row_norms_sq(self) -> jax.Array:
        sq = None
        for blk in self.blocks:
            sb = blk.row_norms_sq()
            sq = sb if sq is None else sq + sb
        if self.hot_matrix is not None:
            sq = sq + jnp.sum(self.hot_matrix * self.hot_matrix, axis=-1)
        return sq

    def to_dense(self):
        from photon_ml_tpu.ops.features import DenseFeatures

        mats = [np.asarray(blk.to_dense().matrix) for blk in self.blocks]
        # pinned grid layouts give uniform block widths that may overhang
        # the true column count; trim like rmatvec does
        dense = np.concatenate(mats, axis=1)[:, : self.num_cols_]
        if self.hot_matrix is not None:
            dense[:, np.asarray(self.hot_cols)] += np.asarray(self.hot_matrix)
        return DenseFeatures(matrix=jnp.asarray(dense))


@struct.dataclass
class _ZeroColumnsBlock:
    """A column block with no entries: all maps are exact zeros."""

    num_rows_: int = struct.field(pytree_node=False)
    num_cols_: int = struct.field(pytree_node=False)

    @property
    def num_rows(self) -> int:
        return self.num_rows_

    @property
    def dim(self) -> int:
        return self.num_cols_

    def matvec(self, w: jax.Array) -> jax.Array:
        return jnp.zeros((self.num_rows_,), dtype=w.dtype)

    def rmatvec(self, c: jax.Array) -> jax.Array:
        return jnp.zeros((self.num_cols_,), dtype=c.dtype)

    rmatvec_sq = rmatvec

    def row_norms_sq(self) -> jax.Array:
        return jnp.zeros((self.num_rows_,), dtype=jnp.float32)

    def to_dense(self):
        from photon_ml_tpu.ops.features import DenseFeatures

        return DenseFeatures(
            matrix=jnp.zeros((self.num_rows_, self.num_cols_), jnp.float32)
        )


# One spilled (over-cap) entry costs about this many routed slots. A COO
# gather + scatter-add runs ~7-10 ns/entry on TPU (SCALING.md measurement)
# while a routed slot moves ~45 B through ~2m+1 kernel passes — ~2 ns at
# the currently-achieved ~25 GB/s but ~0.06 ns at peak HBM, so the right
# ratio is bandwidth-dependent. The default 32 is conservative (prefers
# routing over spill when in doubt); PHOTON_SPILL_SLOT_COST lets the
# hardware measurement session calibrate it. Keeping this a COST (not a
# hard budget) is what lets a thin-tailed 2^26-column shard take a small
# cap + split instead of a 16x-padded flat network (the r5 planner fix).
def _spill_slot_cost() -> int:
    try:
        return max(int(os.environ.get("PHOTON_SPILL_SLOT_COST", "32")), 1)
    except ValueError:
        return 32


# Hard sanity bound: spill stays a small fraction of nnz so the device COO
# arrays and the scatter remain negligible next to the routed network.
_MAX_SPILL_FRACTION = 8  # spill <= nnz / 8


def plan_column_layout(
    col_counts: np.ndarray,
    n: int,
    d: int,
    K: int,
    kp_full: int,
    max_blocks: int = 16,
    size_floor: int = 0,
    row_block_k: Optional["callable"] = None,
    spill_scale: float = 1.0,
):
    """Jointly pick (kp_cap, n_col_blocks) minimizing total cost in routed
    slots, where over-cap (spilled) entries are priced at SPILL_SLOT_COST
    slots each.

    The levers interact through the coarse valid-size ladder (c*128^k,
    c in {1,2,4,8}): capping KP alone may not cross a ladder step, and
    splitting alone multiplies the uncapped d*KP. Candidates: every
    power-of-two cap whose spill stays under nnz/8, crossed with block
    counts {1,2,...,max_blocks}. ``row_block_k(t)`` optionally returns the
    true per-block row group size for a t-way column split (each block
    holds only its columns' entries, so its K is smaller than the global
    K); without it the global K bounds the row side. ``spill_scale``
    normalizes the spill cost to the network-size units: a multi-tile grid
    passes counts concatenated over all tiles while n/d describe ONE tile,
    so it passes 1/num_tiles to keep both sides per-tile. Returns
    ``(cap_or_None, n_blocks)``; a multi-block layout must beat the plain
    one by >= 2x in total cost to justify the extra dispatches.
    """
    nnz = int(col_counts.sum())
    s_plain = routing.valid_size(max(n * K, d * kp_full, size_floor, 1))
    if not nnz or (kp_full <= 1 and d <= 1):
        return None, 1
    max_spill = max(nnz // _MAX_SPILL_FRACTION, 4096)
    cands = []
    p = 1
    while p < kp_full:
        cands.append(p)
        p *= 2
    cands.append(kp_full)  # the uncapped candidate (spill 0), ALWAYS kept
    # columns by degree: a cap's spill from the histogram, one pass over the
    # columns in all and not three a candidate (21 s of a 2 x 2 grid's 1.6e8)
    degree_hist = np.bincount(np.asarray(col_counts, dtype=np.int64))
    degrees = np.arange(degree_hist.size, dtype=np.int64)
    caps = []  # (cap, spill_cost)
    for p in cands:
        spill = (
            0 if p >= kp_full
            else int((np.maximum(degrees - p, 0) * degree_hist).sum())
        )
        if spill <= max_spill:
            caps.append((p, spill * _spill_slot_cost() * spill_scale))
    best = (None, 1, s_plain)
    for cap, spill_cost in caps:
        t = 1
        while t <= max_blocks:
            d_b = -(-d // t)
            k_t = row_block_k(t) if (row_block_k and t > 1) else K
            s_t = t * routing.valid_size(
                max(n * k_t, d_b * cap, size_floor, 1)
            ) + spill_cost
            if s_t < best[2]:
                best = (None if cap >= kp_full else cap, t, s_t)
            t *= 2
    cap, t, s_best = best
    if t > 1 and s_best * 2 > s_plain:
        # a multi-block layout must be a clear (2x) win; fall back to the
        # best single-block layout if capping alone still helps
        best_cap, best_cost = None, s_plain
        for cap, spill_cost in caps:
            if cap >= kp_full:
                continue
            cost = routing.valid_size(
                max(n * K, d * cap, size_floor, 1)
            ) + spill_cost
            if cost < best_cost:
                best_cap, best_cost = cap, cost
        return best_cap, 1
    return cap, t


def make_row_block_k(rows, cols, n: int, d: int, pow2: bool = False):
    """Per-block row group size estimator for the layout planner: for a
    t-way column split, the max nnz any single row holds within one block
    (each block sees only its columns' entries, so its ELL width K is
    smaller than the global K). Memoized per t; ``pow2`` rounds up for the
    fused engine's power-of-two slot groups."""
    cache: dict = {}

    def row_block_k(t: int) -> int:
        if t not in cache:
            d_b = -(-d // t)
            key = rows * t + (cols // d_b)
            # unique, not bincount: memory stays O(nnz) (a bincount over
            # n*t bins would transiently allocate ~13 GB at n=1e8, t=16)
            if key.size:
                _, counts = np.unique(key, return_counts=True)
                k = int(counts.max())
            else:
                k = 1
            if pow2:
                k = 1 << max(int(k) - 1, 0).bit_length()
            cache[t] = max(k, 1)
        return cache[t]

    return row_block_k


def resolve_kp_cap(
    kp_cap,
    col_counts: np.ndarray,
    n: int,
    d: int,
    K: int,
    kp_full: int,
    size_floor: int = 0,
) -> Optional[int]:
    """Normalize a ``kp_cap`` argument ("auto" | int | None/0) to an
    effective cap strictly below ``kp_full``, or None."""
    if not kp_cap:
        return None
    if kp_cap == "auto":
        return auto_kp_cap(col_counts, n, d, K, kp_full, size_floor)
    cap = int(kp_cap)
    if cap <= 0 or cap >= kp_full:
        return None
    if cap & (cap - 1):
        raise ValueError(f"kp_cap={cap} must be a power of two (or 'auto')")
    return cap


def build_column_split(
    builder,
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n: int,
    d: int,
    t: int,
    cap: Optional[int],
    hot_matrix: Optional[np.ndarray],
    hot_ids: Optional[np.ndarray],
    plan_cache: Optional[str],
) -> ColumnSplitFeatures:
    """Partition COLD entries into ``t`` column blocks and build each with
    ``builder`` (a from_coo-compatible callable); the hot side stays global.
    Shared by the stage-by-stage and fused engines. The cut is host layout
    work: a ``route/layout`` span a block."""
    d_b = -(-d // t)
    bounds = [min(b * d_b, d) for b in range(t + 1)]
    with span("route/layout", nnz=int(rows.size), blocks=t):
        blk_of = cols // d_b
    blocks = []
    for b in range(t):
        width = bounds[b + 1] - bounds[b]
        with span("route/layout", nnz=int(rows.size), blocks=t):
            m = blk_of == b
            cut = (rows[m], cols[m] - bounds[b], vals[m])
        if width <= 0 or not cut[0].size:
            blocks.append(_ZeroColumnsBlock(num_rows_=n, num_cols_=max(width, 0)))
            continue
        blocks.append(
            builder(
                *cut, (n, width), plan_cache=plan_cache, max_hot_cols=0,
                kp_cap=cap, col_split=1,
            )
        )
    hot_side = (None, None) if hot_ids is None else upload(
        "features", lambda: _hot_arrays(hot_matrix, hot_ids)
    )
    return ColumnSplitFeatures(
        blocks=tuple(blocks),
        hot_matrix=hot_side[0],
        hot_cols=hot_side[1],
        col_bounds=tuple(bounds),
        num_rows_=int(n),
        num_cols_=int(d),
    )


def _best_split(
    n: int, d: int, K: int, kp_eff: int, max_blocks: int = 16,
    size_floor: int = 0,
) -> int:
    """Best block count for a FIXED effective KP (2x-win hysteresis)."""
    s_one = routing.valid_size(max(n * K, d * kp_eff, size_floor, 1))
    best_t, best_s = 1, s_one
    t = 2
    while t <= max_blocks:
        s_t = t * routing.valid_size(
            max(n * K, -(-d // t) * kp_eff, size_floor, 1)
        )
        if s_t < best_s:
            best_t, best_s = t, s_t
        t *= 2
    return best_t if best_s * 2 <= s_one else 1


def resolve_layout(kp_cap, col_split, col_counts, n, d, K, kp_full,
                   size_floor: int = 0, row_block_k=None,
                   spill_scale: float = 1.0):
    """Normalize (kp_cap, col_split) arguments to an effective
    ``(cap_or_None, n_blocks)`` layout. "auto"/"auto" runs the joint
    planner; manual values are validated and used as-is."""
    if kp_cap == "auto" and col_split == "auto":
        return plan_column_layout(
            col_counts, n, d, K, kp_full, size_floor=size_floor,
            row_block_k=row_block_k, spill_scale=spill_scale,
        )
    cap = resolve_kp_cap(kp_cap, col_counts, n, d, K, kp_full, size_floor)
    if col_split == "auto":
        t = _best_split(n, d, K, cap or kp_full, size_floor=size_floor)
    else:
        t = max(int(col_split or 1), 1)
        if t > 1 and t & (t - 1):
            raise ValueError(f"col_split={t} must be a power of two")
    return cap, t


def from_coo(
    rows,
    cols,
    vals,
    shape,
    max_nnz_row: Optional[int] = None,
    plan_cache: Optional[str] = None,
    hot_col_threshold: Optional[int] = None,
    max_hot_cols: int = 128,
    kp_cap="auto",
    col_split="auto",
):
    """Build from COO triplets (host, vectorized numpy + one Benes routing).

    Duplicates are coalesced by summation (scipy COO semantics). The routing
    is the expensive one-time prep step (seconds to ~a minute at 1e7 nnz —
    the analog of the reference's one-time RDD dataset build). It is
    memoized keyed on the sparsity pattern: by default in a per-uid tempdir
    (~25 MB-1 GB of .npz per distinct large pattern; set
    ``PHOTON_ML_TPU_PLAN_CACHE`` to another directory, or to "" to disable),
    or pass ``plan_cache`` (a directory) explicitly.

    Columns with degree > ``hot_col_threshold`` (default: auto — 4x the mean
    column degree, at least 8) are split into a dense MXU side matrix, capped
    at the ``max_hot_cols`` highest-degree columns. Without the split an
    intercept column (degree n) would pad every CSC column to n slots. Pass
    ``max_hot_cols=0`` to disable.

    ``kp_cap`` ("auto" default) additionally bounds the CSC padding KP when
    the column-degree tail is thin, spilling the over-cap entries to a
    scatter-add side (auto/auto runs :func:`plan_column_layout`, which
    prices spill at _spill_slot_cost() slots per entry and bounds it at
    nnz/8); pass None/0 to disable or a power of two to pin the cap.
    ``col_split`` ("auto" default) may
    partition the column space into independent sub-networks when the
    valid-size ladder would otherwise overshoot (see
    :class:`ColumnSplitFeatures`); the result then is a ColumnSplitFeatures.
    """
    n, d = shape
    with span("route/layout", nnz=int(np.size(rows)), blocks=1) as laying:
        rows, cols, vals, hot_matrix, hot_ids, row_counts, col_counts = (
            prepare_cold_entries(
                rows, cols, vals, shape, max_nnz_row, hot_col_threshold,
                max_hot_cols,
            )
        )
        nnz = rows.size
        k_needed = int(row_counts.max()) if nnz else 1
        # max_nnz_row doubles as a K floor so callers get shape-stable [n, K]
        # ELL arrays across datasets (one jit compilation serves them all).
        K = max(k_needed, int(max_nnz_row) if max_nnz_row is not None else 1, 1)
        KP = max(int(col_counts.max()) if nnz else 1, 1)

        cap, t = (None, 1)
        if nnz:
            cap, t = resolve_layout(
                kp_cap, col_split, col_counts, n, d, K, KP,
                row_block_k=make_row_block_k(rows, cols, n, d),
            )
        laying.set_attrs(blocks=t)
        spill = (None, None, None)
        if t == 1 and cap is not None:
            rows, cols, vals, sr, sc, sv = split_spill_entries(
                rows, cols, vals, col_counts, cap
            )
            spill = (sr, sc, sv)
            row_counts = np.bincount(rows, minlength=n)
            col_counts = np.minimum(col_counts, cap)
            KP = cap
    if t > 1:
        return build_column_split(
            from_coo, rows, cols, vals, n, d, t, cap,
            hot_matrix, hot_ids, plan_cache,
        )

    return _assemble(
        rows, cols, vals, n, d, K, KP, hot_matrix, hot_ids, plan_cache,
        row_counts=row_counts, col_counts=col_counts, spill=spill,
    )


def prepare_cold_entries(
    rows,
    cols,
    vals,
    shape,
    max_nnz_row: Optional[int],
    hot_col_threshold: Optional[int],
    max_hot_cols: int,
):
    """Shared builder prologue: coalesce, validate ``max_nnz_row``, split hot
    columns, count degrees. Returns ``(rows, cols, vals, hot_matrix, hot_ids,
    row_counts, col_counts)`` with rows/cols/vals reduced to cold entries.
    Used by both permutation engines so their data prep stays in lockstep.
    """
    n, d = shape
    rows, cols, vals = coalesce_coo(rows, cols, vals, n, d)

    nnz = rows.size
    if max_nnz_row is not None and nnz:
        k_orig = int(np.bincount(rows, minlength=n).max())
        if k_orig > int(max_nnz_row):
            raise ValueError(
                f"row with {k_orig} nnz exceeds max_nnz_row={max_nnz_row}"
            )

    hot_ids = select_hot_cols(
        rows, cols, n, d, hot_col_threshold, max_hot_cols
    )
    hot_matrix = None
    if hot_ids is not None:
        rows, cols, vals, hot_matrix = split_hot_entries(
            rows, cols, vals, n, d, hot_ids
        )
        nnz = rows.size

    row_counts = np.bincount(rows, minlength=n) if nnz else np.zeros(n, np.int64)
    col_counts = np.bincount(cols, minlength=d) if nnz else np.zeros(d, np.int64)
    return rows, cols, vals, hot_matrix, hot_ids, row_counts, col_counts


def auto_kp_cap(
    col_counts: np.ndarray,
    n: int,
    d: int,
    K: int,
    kp_full: int,
    size_floor: int = 0,
) -> Optional[int]:
    """Pick a power-of-two cap on the CSC slot-group size KP, or None.

    The routed network is sized S = valid_size(max(n*K, d*KP, floor)). When
    column degrees have a thin tail (e.g. the 1B-coefficient grid shard:
    mean degree ~1, max ~12), KP = max degree pads the network by the
    max/mean ratio. Capping KP and spilling each column's entries beyond the
    cap to a tiny COO side (scatter-add at evaluation) shrinks S by that
    ratio. The cap is the smallest power of two whose spill stays under
    nnz/128 (scatter cost negligible next to the routed passes), applied
    only when it actually shrinks S.
    """
    nnz = int(col_counts.sum())
    if not nnz or kp_full <= 1:
        return None
    s_now = routing.valid_size(max(n * K, d * kp_full, size_floor, 1))
    budget = max(nnz // 128, 4096)
    p = 1
    while p < kp_full:
        spill = int(np.maximum(col_counts - p, 0).sum())
        if spill <= budget:
            s_new = routing.valid_size(max(n * K, d * p, size_floor, 1))
            return p if s_new < s_now else None
        p *= 2
    return None


def split_spill_entries(rows, cols, vals, col_counts: np.ndarray, cap: int):
    """Split entries so every column keeps at most ``cap`` routed entries.

    Returns ``(cold_rows, cold_cols, cold_vals, spill_rows, spill_cols,
    spill_vals)``. Kept entries are each column's first ``cap`` in (col,
    row) order — deterministic for plan-cache stability.
    """
    nnz = rows.size
    corder = lexsort_pairs(cols, rows)
    col_starts = np.zeros(col_counts.size + 1, dtype=np.int64)
    np.cumsum(col_counts, out=col_starts[1:])
    rank = np.arange(nnz, dtype=np.int64) - col_starts[cols[corder]]
    spill_sorted = rank >= cap
    spill = np.zeros(nnz, dtype=bool)
    spill[corder] = spill_sorted
    keep = ~spill
    return (
        rows[keep], cols[keep], vals[keep],
        rows[spill], cols[spill], vals[spill],
    )


def _hot_arrays(hot_matrix, hot_ids):
    """Device arrays for a hot side (None, None when there is none)."""
    if hot_ids is None:
        return None, None
    return jnp.asarray(hot_matrix), jnp.asarray(hot_ids, dtype=jnp.int32)


def _spill_arrays(spill_rows, spill_cols, spill_vals):
    """Device arrays for a spill side (None when empty)."""
    if spill_rows is None or spill_rows.size == 0:
        return None, None, None
    return (
        jnp.asarray(spill_rows, dtype=jnp.int32),
        jnp.asarray(spill_cols, dtype=jnp.int32),
        jnp.asarray(spill_vals, dtype=jnp.float32),
    )


def coalesce_coo(rows, cols, vals, n: int, d: int):
    """Validate index ranges and coalesce duplicate (row, col) entries by
    summation (scipy COO semantics; accumulation in float64)."""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float32)
    if rows.size:
        if rows.min() < 0 or rows.max() >= n:
            raise ValueError(f"row index out of range [0, {n})")
        if cols.min() < 0 or cols.max() >= d:
            raise ValueError(f"column index out of range [0, {d})")
        order = lexsort_pairs(rows, cols)
        rows, cols, vals = rows[order], cols[order], vals[order]
        boundary = np.empty(rows.size, dtype=bool)
        boundary[0] = True
        boundary[1:] = (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])
        seg = np.cumsum(boundary) - 1
        summed = np.zeros(int(boundary.sum()), dtype=np.float64)
        np.add.at(summed, seg, vals)
        rows, cols = rows[boundary], cols[boundary]
        vals = summed.astype(np.float32)
    return rows, cols, vals


def select_hot_cols(
    rows: np.ndarray,
    cols: np.ndarray,
    n_rows_per_shard: int,
    d: int,
    hot_col_threshold: Optional[int],
    max_hot_cols: int,
) -> Optional[np.ndarray]:
    """Pick the hot-column set (sorted ids) or None.

    A column only qualifies when densifying it is actually cheap: degree
    >= n/16 bounds the dense-storage inflation at 16x the entries moved
    (mildly-hot columns would waste n floats each for little KP relief).
    The n*H dense block is further capped at ~512 MB. ``n_rows_per_shard``
    is the dense side's row count (the local row count for sharded data).
    """
    nnz = rows.size
    if not nnz or max_hot_cols <= 0:
        return None
    col_counts_all = np.bincount(cols, minlength=d)
    if hot_col_threshold is None:
        thr = max(8, int(4 * np.ceil(nnz / max(d, 1))), n_rows_per_shard // 16)
    else:
        thr = int(hot_col_threshold)
    h_cap = min(
        int(max_hot_cols), max(1, (128 << 20) // max(n_rows_per_shard, 1))
    )
    hot_mask = col_counts_all > thr
    n_hot = int(hot_mask.sum())
    if n_hot > h_cap:
        top = np.argpartition(col_counts_all, -h_cap)[-h_cap:]
        return np.sort(top)
    if n_hot > 0:
        return np.flatnonzero(hot_mask)
    return None


def split_hot_entries(rows, cols, vals, n: int, d: int, hot_ids: np.ndarray):
    """Split entries into (cold rows/cols/vals, dense [n, H] hot matrix)."""
    hot_pos = np.full(d, -1, dtype=np.int64)
    hot_pos[hot_ids] = np.arange(hot_ids.size)
    is_hot = hot_pos[cols] >= 0
    hot_matrix = np.zeros((n, hot_ids.size), dtype=np.float32)
    hot_matrix[rows[is_hot], hot_pos[cols[is_hot]]] = vals[is_hot]
    return rows[~is_hot], cols[~is_hot], vals[~is_hot], hot_matrix


def build_slot_perm(
    rows: np.ndarray,
    cols: np.ndarray,
    n: int,
    d: int,
    K: int,
    KP: int,
    S: int,
    row_counts: np.ndarray,
    col_counts: np.ndarray,
):
    """(ell_pos, csc_pos, perm) for one routed layout.

    ell_pos[e]: ELL slot of entry e (row-major position row*K + slot).
    csc_pos[e]: CSC slot of entry e (column-major position col*KP + slot).
    perm: bijection on [0, S) with perm[q] = p for real entries and pads
    mapped to pads in ascending order. Shared by the stage-by-stage and
    fused engines so both route identical networks for one pattern.
    """
    nnz = rows.size
    row_starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(row_counts, out=row_starts[1:])
    ell_slot = np.arange(nnz, dtype=np.int64) - row_starts[rows]
    ell_pos = rows * K + ell_slot

    corder = lexsort_pairs(cols, rows)
    col_starts = np.zeros(d + 1, dtype=np.int64)
    np.cumsum(col_counts, out=col_starts[1:])
    csc_slot = np.arange(nnz, dtype=np.int64) - col_starts[cols[corder]]
    csc_pos_sorted = cols[corder] * KP + csc_slot
    csc_pos = np.empty(nnz, dtype=np.int64)
    csc_pos[corder] = csc_pos_sorted

    perm = np.full(S, -1, dtype=np.int64)
    perm[csc_pos] = ell_pos
    free_dst = np.flatnonzero(perm < 0)
    used_src = np.zeros(S, dtype=bool)
    used_src[ell_pos] = True
    perm[free_dst] = np.flatnonzero(~used_src)
    return ell_pos, csc_pos, perm


def route_layout(
    rows: np.ndarray,
    cols: np.ndarray,
    n: int,
    d: int,
    K: int,
    KP: int,
    plan_cache: Optional[str],
    size_floor: int = 0,
    row_counts: Optional[np.ndarray] = None,
    col_counts: Optional[np.ndarray] = None,
):
    """Shared routing core for both permutation engines: validate pinned
    paddings, size the network, build slot positions and the (plan,
    plan_inv) pair. Returns ``(ell_pos, csc_pos, plan, plan_inv, S)``.
    Spans ``route/slot_perm`` (``slots``), ``route/plan`` and
    ``route/place`` (the inverse plan)."""
    nnz = rows.size
    S = routing.valid_size(max(n * K, d * KP, size_floor, 1))
    with span("route/slot_perm", slots=S):
        if row_counts is None:
            row_counts = (
                np.bincount(rows, minlength=n) if nnz else np.zeros(n, np.int64)
            )
        if col_counts is None:
            col_counts = (
                np.bincount(cols, minlength=d) if nnz else np.zeros(d, np.int64)
            )
        assert not nnz or (
            row_counts.max() <= K and col_counts.max() <= KP
        ), "pinned paddings smaller than actual degrees"
        ell_pos, csc_pos, perm = build_slot_perm(
            rows, cols, n, d, K, KP, S, row_counts, col_counts
        )
    plan = _build_plan_cached(perm, plan_cache)
    with span("route/place"):
        plan_inv = plan.invert()
    return ell_pos, csc_pos, plan, plan_inv, S


def _assemble(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n: int,
    d: int,
    K: int,
    KP: int,
    hot_matrix: Optional[np.ndarray],
    hot_ids: Optional[np.ndarray],
    plan_cache: Optional[str],
    size_floor: int = 0,
    row_counts: Optional[np.ndarray] = None,
    col_counts: Optional[np.ndarray] = None,
    spill=(None, None, None),
) -> BenesSparseFeatures:
    """Route + lay out one (cold-entries, hot-side) pair with pinned paddings.

    K/KP/size_floor are caller-pinned so independent shards of one dataset
    can be forced onto identical network shapes (the sharded builder stacks
    them under one compiled program). Callers that already hold the degree
    bincounts pass them to skip a recount. ``spill`` is an optional
    (rows, cols, vals) COO side of over-cap entries (see auto_kp_cap).
    """
    ell_pos, csc_pos, plan, plan_inv, S = route_layout(
        rows, cols, n, d, K, KP, plan_cache, size_floor, row_counts, col_counts
    )

    with span("route/place"):
        ell_values = np.zeros((n, K), dtype=np.float32)
        ell_values.reshape(-1)[ell_pos] = vals
        csc_values = np.zeros((d, KP), dtype=np.float32)
        csc_values.reshape(-1)[csc_pos] = vals

    def features():
        hm, hc = _hot_arrays(hot_matrix, hot_ids)
        sr, sc, sv = _spill_arrays(*spill)
        return dict(
            ell_values=jnp.asarray(ell_values), csc_values=jnp.asarray(csc_values),
            hot_matrix=hm, hot_cols=hc, spill_rows=sr, spill_cols=sc, spill_vals=sv,
        )

    return BenesSparseFeatures(
        **upload("features", features),
        **upload("plan", lambda: dict(
            plan=device_plan(plan), plan_inv=device_plan(plan_inv)
        )),
        num_rows_=int(n),
        num_cols_=int(d),
    )


def from_ell(ell, plan_cache: Optional[str] = None) -> BenesSparseFeatures:
    """Convert an ``ops.features.EllFeatures`` (host round-trip)."""
    vals = np.asarray(ell.values)
    idx = np.asarray(ell.indices)
    n, k = vals.shape
    live = vals != 0.0
    rows = np.repeat(np.arange(n, dtype=np.int64), k).reshape(n, k)[live]
    return from_coo(
        rows,
        idx[live].astype(np.int64),
        vals[live],
        (n, ell.num_cols),
        max_nnz_row=k,
        plan_cache=plan_cache,
    )


def _build_plan_cached(perm: np.ndarray, cache_dir: Optional[str]):
    """The routed plan of ``perm``, read back from the plan cache where it
    holds one, else routed (and written there). Span ``route/plan``:
    ``cached`` says which, ``bytes`` is the plan file's size (0 where the
    cache is off)."""
    with span("route/plan", slots=int(perm.shape[0])) as planning:
        plan, cached, path = _plan_of(perm, cache_dir)
        planning.set_attrs(
            cached=cached, bytes=path.stat().st_size if path is not None else 0
        )
    return plan


def _plan_of(perm: np.ndarray, cache_dir: Optional[str]):
    """(plan, read from the cache, its file or None)."""
    if cache_dir is None:
        cache_dir = default_plan_cache()
    if not cache_dir:  # None or "" — disabled
        return routing.build_plan(perm), False, None
    import hashlib
    from pathlib import Path

    h = hashlib.sha1(perm.tobytes()).hexdigest()[:16]
    # v2: int8 stage indices. Bump on any plan-format or routing change so
    # stale entries from older code can never be served.
    path = Path(cache_dir) / f"benesplan_v2_{perm.shape[0]}_{h}.npz"
    if path.exists():
        try:
            plan = _load_plan_file(path)
        except Exception:
            plan = None  # unreadable/foreign entry: rebuild and overwrite
        if plan is not None:
            return plan, True, path

    plan = routing.build_plan(perm)
    arrays = {"size": np.int64(plan.size)}
    kinds = []
    i = 0
    for st in plan.stages:
        if isinstance(st, routing.LaneShuffle):
            kinds.append("lane")
            # lane/sublane indices are < 128/8: int8 storage quarters the
            # on-disk plan (the device uses int8 anyway, permute_net.py)
            arrays[f"idx{i}"] = st.idx.astype(np.int8)
            i += 1
        elif isinstance(st, routing.SublaneShuffle):
            kinds.append(f"sublane:{st.rows}")
            arrays[f"idx{i}"] = st.idx.astype(np.int8)
            i += 1
        elif isinstance(st, routing.Enter):
            kinds.append(f"enter:{st.blocks}:{st.rows}")
        else:
            kinds.append(f"leave:{st.blocks}:{st.rows}")
    arrays["kinds"] = np.array(kinds)
    path.parent.mkdir(parents=True, exist_ok=True)
    # atomic publish: concurrent builders of the same pattern must never
    # read a half-written file
    import os
    import tempfile as _tf

    fd, tmp = _tf.mkstemp(dir=str(path.parent), suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    # retire the pre-versioning (v1, int32) entry for this pattern, if any
    try:
        os.unlink(str(Path(cache_dir) / f"benesplan_{perm.shape[0]}_{h}.npz"))
    except OSError:
        pass
    return plan, False, path


def _load_plan_file(path) -> routing.PermPlan:
    data = np.load(path)
    stages: list = []
    i = 0
    for kind in data["kinds"]:
        kind = kind.decode() if isinstance(kind, bytes) else str(kind)
        parts = kind.split(":")
        if parts[0] == "lane":
            stages.append(routing.LaneShuffle(idx=data[f"idx{i}"]))
            i += 1
        elif parts[0] == "sublane":
            stages.append(
                routing.SublaneShuffle(idx=data[f"idx{i}"], rows=int(parts[1]))
            )
            i += 1
        elif parts[0] == "enter":
            stages.append(routing.Enter(int(parts[1]), int(parts[2])))
        elif parts[0] == "leave":
            stages.append(routing.Leave(int(parts[1]), int(parts[2])))
        else:
            raise ValueError(f"unknown cached stage kind {kind!r}")
    return routing.PermPlan(size=int(data["size"]), stages=stages)


def default_plan_cache() -> Optional[str]:
    """Default routing-plan cache directory: $PHOTON_ML_TPU_PLAN_CACHE, or a
    per-uid 0700 tempdir. Set the env var to "" to disable caching. Plans
    are keyed by the sha1 of the permutation plus a format version; entries
    that fail to load are rebuilt, so only disk space is at stake (~0.1 GB
    per distinct large pattern)."""
    import os

    from photon_ml_tpu.utils.cachedir import per_uid_cache_dir

    env = os.environ.get("PHOTON_ML_TPU_PLAN_CACHE")
    if env is not None:
        return env or None
    return per_uid_cache_dir("photon_ml_tpu_plan_cache")
