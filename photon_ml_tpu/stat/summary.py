"""Per-feature summary statistics for normalization and diagnostics.

Reference parity: stat/BasicStatisticalSummary.scala:50, which wrapped Spark
MLlib's MultivariateOnlineSummarizer (weighted mean/variance/min/max/nnz/count)
computed with a treeAggregate. Here it is one jit-compiled pass over the batch
— and because every op is a reduction over the batch axis, running it on
data sharded over a mesh's batch axis makes XLA insert the psums automatically.

Variance is the unbiased weighted sample variance matching MLlib's estimator
so normalization factors line up with the reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.ops.features import DenseFeatures, EllFeatures
from photon_ml_tpu.telemetry.span import span


@struct.dataclass
class BasicStatisticalSummary:
    mean: jax.Array           # [d] weighted mean
    variance: jax.Array       # [d] unbiased weighted variance
    num_nonzeros: jax.Array   # [d] weighted count of nonzero entries
    max_abs: jax.Array        # [d] max |x| (0 for all-zero features)
    min_val: jax.Array        # [d] min over observed values incl. implicit zeros
    max_val: jax.Array        # [d] max over observed values incl. implicit zeros
    count: jax.Array          # scalar total weight
    mean_abs: jax.Array       # [d] weighted mean of |x| (reference meanAbs,
    #                           used by ExpectedMagnitude feature importance)


def _dense_stats(matrix, weights):
    wsum = jnp.sum(weights)
    w = weights[:, None]
    s1 = jnp.sum(w * matrix, axis=0)
    s2 = jnp.sum(w * matrix * matrix, axis=0)
    sabs = jnp.sum(w * jnp.abs(matrix), axis=0)
    nnz = jnp.sum(jnp.where(matrix != 0, w, 0.0), axis=0)
    mx = jnp.max(jnp.where(weights[:, None] > 0, matrix, -jnp.inf), axis=0)
    mn = jnp.min(jnp.where(weights[:, None] > 0, matrix, jnp.inf), axis=0)
    return s1, s2, sabs, nnz, mn, mx, wsum


def _ell_stats(feats: EllFeatures, weights):
    d = feats.num_cols
    wsum = jnp.sum(weights)
    w = weights[:, None]
    wv = w * feats.values
    zeros = lambda: jnp.zeros((d,), dtype=feats.values.dtype)
    s1 = zeros().at[feats.indices].add(wv)
    s2 = zeros().at[feats.indices].add(wv * feats.values)
    sabs = zeros().at[feats.indices].add(jnp.abs(wv))
    nnz = zeros().at[feats.indices].add(jnp.where(feats.values != 0, w, 0.0))
    # min/max over EXPLICIT values; implicit zeros folded in afterwards
    mx = jnp.full((d,), -jnp.inf, dtype=feats.values.dtype).at[feats.indices].max(
        jnp.where((feats.values != 0) & (w > 0), feats.values, -jnp.inf)
    )
    mn = jnp.full((d,), jnp.inf, dtype=feats.values.dtype).at[feats.indices].min(
        jnp.where((feats.values != 0) & (w > 0), feats.values, jnp.inf)
    )
    return s1, s2, sabs, nnz, mn, mx, wsum


def _benes_stats(feats, weights):
    """Stats through the permutation engine's own linear maps: the weighted
    sums are rmatvec-style reductions; min/max route the row-weight mask to
    the column-grouped side once and reduce per column there."""
    d = feats.dim
    wsum = jnp.sum(weights)
    ell = feats.ell_values
    hot = feats.hot_matrix
    sp = feats.spill_vals
    s1 = feats.rmatvec(weights)
    s2 = feats.rmatvec_sq(weights)
    sabs = feats._rmatvec_impl(
        jnp.abs(ell), None if hot is None else jnp.abs(hot), weights,
        None if sp is None else jnp.abs(sp),
    )
    nnz = feats._rmatvec_impl(
        (ell != 0).astype(ell.dtype),
        None if hot is None else (hot != 0).astype(ell.dtype),
        weights,
        None if sp is None else (sp != 0).astype(ell.dtype),
    )
    # live-row mask routed to CSC slot order: explicit entries of columns
    # are contiguous there, so per-column min/max are row reductions
    n, k = ell.shape
    mask_ell = jnp.broadcast_to((weights > 0)[:, None], (n, k)).astype(ell.dtype)
    mask_flat = feats._pad_ell(mask_ell.reshape(-1))
    dkp = feats.csc_values.shape[0] * feats.csc_values.shape[1]
    mask_csc = feats._to_csc(mask_flat)[:dkp].reshape(feats.csc_values.shape)
    live = (feats.csc_values != 0) & (mask_csc > 0)
    mx = jnp.max(
        jnp.where(live, feats.csc_values, -jnp.inf), axis=1
    )
    mn = jnp.min(
        jnp.where(live, feats.csc_values, jnp.inf), axis=1
    )
    mn, mx = _fold_hot_minmax(mn, mx, hot, feats.hot_cols, weights)
    mn, mx = _fold_spill_minmax(mn, mx, feats, weights)
    return s1, s2, sabs, nnz, mn, mx, wsum


def _grouped(op: str, flat, group: int):
    """``op`` ("max" or "min") over each run of ``group`` adjacent entries of
    ``flat``; ``group`` is a power of two and ``flat.size`` a multiple of 128.
    Never through a ``[n, group]`` array: on a TPU a minor dimension of 2 is
    padded to 128 lanes (2.56 GB for a block of 5M columns) and XLA took
    minutes to compile a reduction over it, at every eager call (so did
    ``jnp.repeat`` of the row weights to their 16 slots each): a
    ``summarize`` over 8 routed blocks of 40M columns took 288 s on a v5e
    (chip, PR 28). Here runs are folded along the lanes by rolls, and every
    ``group``-th lane is kept."""
    lanes = 128
    if group >= lanes:
        return getattr(flat.reshape(-1, group), op)(axis=1)
    pairwise = jnp.maximum if op == "max" else jnp.minimum
    x = flat.reshape(-1, lanes)
    shift = 1
    while shift < group:
        x = pairwise(x, jnp.roll(x, -shift, axis=1))
        shift *= 2
    return x[:, ::group].reshape(-1)


@jax.jit
def _fused_stats(feats, weights):
    """Stats through the fused engine's transformed linear maps; min/max
    route the live rows' values to the column-grouped side once (an rmatvec
    without its reduction) and fold each column's slots there. One compiled
    program per block shape: run eagerly, every kernel was compiled again at
    each call, and each of the spill side's three scatters cost the TPU
    compiler 7 s (it sorts the indices) for every new spill length."""
    wsum = jnp.sum(weights)
    s1 = feats.rmatvec(weights)
    s2 = feats.rmatvec_sq(weights)
    sabs = feats._rmatvec_impl(weights, transform="abs")
    nnz = feats._rmatvec_impl(weights, transform="nnz")

    # the values of live rows on the column-grouped side; 0 marks a pad, a
    # stored zero or a weight-0 row, none of which is an observed value
    values = feats.routed_values((weights > 0).astype(feats.ell_flat.dtype))
    big = jnp.asarray(jnp.inf, values.dtype)
    d, kp = feats.dim, feats.csc_k
    mx = _grouped("max", jnp.where(values != 0, values, -big), kp)[:d]
    mn = _grouped("min", jnp.where(values != 0, values, big), kp)[:d]
    mn, mx = _fold_hot_minmax(mn, mx, feats.hot_matrix, feats.hot_cols, weights)
    mn, mx = _fold_spill_minmax(mn, mx, feats, weights)
    return s1, s2, sabs, nnz, mn, mx, wsum


def _pad_spill(feats, length: int):
    """``feats`` with its spill side padded to ``length`` entries of value 0
    (no-ops in every statistic): the blocks of one column split then have
    the same shapes and share ``_fused_stats``'s one compiled program."""
    if feats.spill_rows is None or feats.spill_rows.shape[0] == length:
        return feats
    pad = lambda a: jnp.pad(a, (0, length - a.shape[0]))  # noqa: E731
    return feats.replace(
        spill_rows=pad(feats.spill_rows),
        spill_cols=pad(feats.spill_cols),
        spill_vals=pad(feats.spill_vals),
    )


def _split_stats(feats, weights):
    """Stats for a ColumnSplitFeatures: per-block engine stats concatenated
    on the column axis, the global hot side folded in afterwards."""
    from photon_ml_tpu.ops.fused_perm import FusedBenesFeatures
    from photon_ml_tpu.ops.sparse_perm import (
        BenesSparseFeatures,
        _ZeroColumnsBlock,
    )

    wsum = jnp.sum(weights)
    longest_spill = max(
        (b.spill_rows.shape[0] for b in feats.blocks
         if isinstance(b, FusedBenesFeatures) and b.spill_rows is not None),
        default=0,
    )
    parts = []
    for blk in feats.blocks:
        if isinstance(blk, _ZeroColumnsBlock):
            d_b = blk.num_cols_
            z = jnp.zeros((d_b,), dtype=jnp.float32)
            parts.append((
                z, z, z, z,
                jnp.full((d_b,), jnp.inf, dtype=jnp.float32),
                jnp.full((d_b,), -jnp.inf, dtype=jnp.float32),
                wsum,
            ))
        elif isinstance(blk, BenesSparseFeatures):
            parts.append(_benes_stats(blk, weights))
        elif isinstance(blk, FusedBenesFeatures):
            parts.append(_fused_stats(_pad_spill(blk, longest_spill), weights))
        else:
            raise TypeError(f"unknown column block type {type(blk)!r}")
    d = feats.num_cols_
    # pinned grid layouts give uniform block widths that may overhang the
    # true column count; trim like ColumnSplitFeatures.rmatvec does
    s1, s2, sabs, nnz, mn, mx = (
        jnp.concatenate([p[i] for p in parts])[:d] for i in range(6)
    )
    hot = feats.hot_matrix
    if hot is not None:
        w = weights[:, None]
        hc = feats.hot_cols
        s1 = s1.at[hc].add(jnp.sum(w * hot, axis=0))
        s2 = s2.at[hc].add(jnp.sum(w * hot * hot, axis=0))
        sabs = sabs.at[hc].add(jnp.sum(w * jnp.abs(hot), axis=0))
        nnz = nnz.at[hc].add(jnp.sum(jnp.where(hot != 0, w, 0.0), axis=0))
        mn, mx = _fold_hot_minmax(mn, mx, hot, hc, weights)
    return s1, s2, sabs, nnz, mn, mx, wsum


def _fold_spill_minmax(mn, mx, feats, weights):
    """Fold a KP-cap spill side's values into per-column min/max — shared by
    both permutation engines' stats paths."""
    sv = feats.spill_vals
    if sv is None:
        return mn, mx
    live = (sv != 0) & (weights[feats.spill_rows] > 0)
    big = jnp.asarray(jnp.inf, sv.dtype)
    mn = mn.at[feats.spill_cols].min(jnp.where(live, sv, big))
    mx = mx.at[feats.spill_cols].max(jnp.where(live, sv, -big))
    return mn, mx


def _fold_hot_minmax(mn, mx, hot, hot_cols, weights):
    """Fold a hot-column dense side's per-column min/max into (mn, mx) —
    shared by both permutation engines' stats paths."""
    if hot is None:
        return mn, mx
    hlive = (hot != 0) & (weights > 0)[:, None]
    hmx = jnp.max(jnp.where(hlive, hot, -jnp.inf), axis=0)
    hmn = jnp.min(jnp.where(hlive, hot, jnp.inf), axis=0)
    return mn.at[hot_cols].min(hmn), mx.at[hot_cols].max(hmx)


def summarize(data: LabeledData) -> BasicStatisticalSummary:
    # device_sync: the pass is dispatched device work, so the span waits for it
    with span(
        "glm/summarize", device_sync=True,
        columns=int(data.dim), engine=type(data.features).__name__,
    ):
        return _summarize(data)


def _summarize(data: LabeledData) -> BasicStatisticalSummary:
    from photon_ml_tpu.ops.fused_perm import FusedBenesFeatures
    from photon_ml_tpu.ops.sparse_perm import (
        BenesSparseFeatures,
        ColumnSplitFeatures,
    )

    feats = data.features
    if isinstance(feats, DenseFeatures):
        s1, s2, sabs, nnz, mn, mx, wsum = _dense_stats(feats.matrix, data.weights)
        sparse = False
    elif isinstance(feats, ColumnSplitFeatures):
        s1, s2, sabs, nnz, mn, mx, wsum = _split_stats(feats, data.weights)
        sparse = True
    elif isinstance(feats, BenesSparseFeatures):
        s1, s2, sabs, nnz, mn, mx, wsum = _benes_stats(feats, data.weights)
        sparse = True
    elif isinstance(feats, FusedBenesFeatures):
        s1, s2, sabs, nnz, mn, mx, wsum = _fused_stats(feats, data.weights)
        sparse = True
    else:
        s1, s2, sabs, nnz, mn, mx, wsum = _ell_stats(feats, data.weights)
        sparse = True

    mean = s1 / jnp.maximum(wsum, 1e-30)
    # unbiased weighted variance (MLlib): (s2 - wsum*mean^2) / (wsum - 1)
    var = jnp.maximum(s2 - wsum * mean * mean, 0.0) / jnp.maximum(wsum - 1.0, 1e-30)

    if sparse:
        # features with implicit zeros extend min/max to include 0
        has_implicit_zero = nnz < wsum
        mx = jnp.where(jnp.isneginf(mx), 0.0, jnp.where(has_implicit_zero, jnp.maximum(mx, 0.0), mx))
        mn = jnp.where(jnp.isposinf(mn), 0.0, jnp.where(has_implicit_zero, jnp.minimum(mn, 0.0), mn))
    else:
        mx = jnp.where(jnp.isneginf(mx), 0.0, mx)
        mn = jnp.where(jnp.isposinf(mn), 0.0, mn)

    max_abs = jnp.maximum(jnp.abs(mx), jnp.abs(mn))
    return BasicStatisticalSummary(
        mean=mean,
        variance=var,
        num_nonzeros=nnz,
        max_abs=max_abs,
        min_val=mn,
        max_val=mx,
        count=wsum,
        mean_abs=sabs / jnp.maximum(wsum, 1e-30),
    )
