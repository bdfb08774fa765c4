"""Feature-matrix representations and the three linear maps every GLM needs.

The reference stores each example as a Breeze sparse vector and runs sparse
axpy per partition (ValueAndGradientAggregator.scala:132-153). On TPU the
equivalent is a struct-of-arrays batch with three primitives:

- ``matvec(w)``    : margins  z = X @ w                 (forward)
- ``rmatvec(c)``   : gradient accumulation  X^T @ c     (reverse)
- ``rmatvec_sq(c)``: Hessian diagonal  (X*X)^T @ c

Two layouts:

- :class:`DenseFeatures` — plain ``[n, d]`` matrix; MXU-friendly, used for the
  small per-entity local problems after index-map projection and for dense
  benchmarks.
- :class:`EllFeatures` — padded row-sparse (ELL) layout ``values/indices
  [n, k]`` with k = max nnz per row; used for the global fixed-effect problem
  where d is huge (up to 1e9) and rows are sparse. matvec is a gather + fused
  multiply-reduce; rmatvec is a scatter-add. Padding slots carry value 0.0 so
  they are algebraic no-ops.

Shapes are strictly 2-D per batch; wrap in ``jax.vmap`` for a leading batch
axis (the random-effect engine does exactly that).
"""

from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp
from flax import struct

from photon_ml_tpu.telemetry.span import upload


@struct.dataclass
class DenseFeatures:
    """Dense ``[n, d]`` feature matrix."""

    matrix: jax.Array

    @property
    def num_rows(self) -> int:
        return self.matrix.shape[0]

    @property
    def dim(self) -> int:
        return self.matrix.shape[1]

    def matvec(self, w: jax.Array) -> jax.Array:
        return self.matrix @ w

    def rmatvec(self, c: jax.Array) -> jax.Array:
        return self.matrix.T @ c

    def rmatvec_sq(self, c: jax.Array) -> jax.Array:
        return (self.matrix * self.matrix).T @ c

    def row_norms_sq(self) -> jax.Array:
        return jnp.sum(self.matrix * self.matrix, axis=-1)


@struct.dataclass
class EllFeatures:
    """Padded row-sparse (ELL) feature matrix.

    values:  [n, k] float — feature values, 0.0 in padding slots.
    indices: [n, k] int32 — column index per slot, 0 in padding slots.
    num_cols: static feature dimension d.
    """

    values: jax.Array
    indices: jax.Array
    num_cols: int = struct.field(pytree_node=False)

    @property
    def num_rows(self) -> int:
        return self.values.shape[0]

    @property
    def dim(self) -> int:
        return self.num_cols

    def matvec(self, w: jax.Array) -> jax.Array:
        # gather w at indices, multiply by values, reduce over the slot axis
        return jnp.sum(self.values * w[self.indices], axis=-1)

    def rmatvec(self, c: jax.Array) -> jax.Array:
        # scatter-add c_i * v_is into column indices; padding contributes 0
        contrib = self.values * c[:, None]
        return jnp.zeros(self.num_cols, dtype=contrib.dtype).at[self.indices].add(contrib)

    def rmatvec_sq(self, c: jax.Array) -> jax.Array:
        contrib = self.values * self.values * c[:, None]
        return jnp.zeros(self.num_cols, dtype=contrib.dtype).at[self.indices].add(contrib)

    def row_norms_sq(self) -> jax.Array:
        return jnp.sum(self.values * self.values, axis=-1)

    def to_dense(self) -> DenseFeatures:
        n = self.num_rows
        dense = jnp.zeros((n, self.num_cols), dtype=self.values.dtype)
        rows = jnp.arange(n)[:, None]
        dense = dense.at[rows, self.indices].add(self.values)
        return DenseFeatures(matrix=dense)


FeatureMatrix = Union[DenseFeatures, EllFeatures]


def _coalesce_coo(rows, cols, vals, n, d):
    """Validate + duplicate-coalesce COO triplets; returns the (possibly
    re-sorted) triplets and the per-row counts. Decoder output is already
    (row, col)-sorted and duplicate-free, so both the lexsort and the
    (slow) np.add.at are skipped on that fast path — this is the streaming
    prefetcher's per-block hot loop."""
    import numpy as np

    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float32)
    if rows.size:
        if rows.min() < 0 or rows.max() >= n:
            raise ValueError(f"row index out of range [0, {n})")
        if d is not None and (cols.min() < 0 or cols.max() >= d):
            raise ValueError(f"column index out of range [0, {d})")
        in_order = bool(
            np.all(
                (rows[1:] > rows[:-1])
                | ((rows[1:] == rows[:-1]) & (cols[1:] >= cols[:-1]))
            )
        )
        if not in_order:
            order = np.lexsort((cols, rows))
            rows, cols, vals = rows[order], cols[order], vals[order]
        boundary = np.empty(rows.size, dtype=bool)
        boundary[0] = True
        boundary[1:] = (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])
        uniq = int(boundary.sum())
        if uniq != rows.size:
            seg_ids = np.cumsum(boundary) - 1
            summed = np.zeros(uniq, dtype=np.float64)
            np.add.at(summed, seg_ids, vals)
            rows, cols = rows[boundary], cols[boundary]
            vals = summed.astype(np.float32)
    counts = np.bincount(rows, minlength=n)
    return rows, cols, vals, counts


def _scatter_ell(rows, cols, vals, counts, values, indices) -> None:
    """Scatter coalesced, (row, col)-sorted triplets into ELL arrays."""
    import numpy as np

    if not rows.size:
        return
    n = values.shape[0]
    # slot index within each row: position minus that row's start offset
    starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    slots = np.arange(rows.size, dtype=np.int64) - starts[rows]
    values[rows, slots] = vals
    indices[rows, slots] = cols


def pack_ell_host(rows, cols, vals, shape, max_nnz: int | None = None):
    """Host-side ELL packing from COO triplets: returns numpy
    ``(values [n, k], indices [n, k])`` without touching the device.

    This is the staging half of :func:`from_scipy_like` — the streaming
    prefetcher packs blocks in a background thread and defers the
    ``device_put`` to the consumer, so packing must not allocate device
    buffers. Semantics are identical: duplicates coalesced by summation,
    ``ValueError`` when a row exceeds ``max_nnz``.
    """
    import numpy as np

    n, d = shape
    rows, cols, vals, counts = _coalesce_coo(rows, cols, vals, n, d)
    needed = int(counts.max()) if rows.size else 1
    k = max(int(max_nnz) if max_nnz is not None else needed, 1)
    if needed > k:
        raise ValueError(
            f"row with {needed} nonzeros exceeds max_nnz={k}; raise max_nnz or "
            "pre-select features"
        )
    values = np.zeros((n, k), dtype=np.float32)
    indices = np.zeros((n, k), dtype=np.int32)
    _scatter_ell(rows, cols, vals, counts, values, indices)
    return values, indices


def pack_ell_into(
    rows, cols, vals, values_out, indices_out, num_cols: int | None = None
) -> None:
    """In-place :func:`pack_ell_host`: scatter COO triplets directly into
    caller-owned, zero-initialized ``[n, k]`` staging arrays.

    The streaming block assembler packs each file piece of a block into
    the block's staging buffers as it arrives — pieces are row-disjoint,
    so piecewise packing is exactly equivalent to packing the whole block
    at once, and the intermediate per-file COO concatenation (one full
    copy of every triplet per block) disappears. Rows previously written
    by another call must not be revisited.
    """
    n, k = values_out.shape
    rows, cols, vals, counts = _coalesce_coo(rows, cols, vals, n, num_cols)
    needed = int(counts.max()) if rows.size else 0
    if needed > k:
        raise ValueError(
            f"row with {needed} nonzeros exceeds max_nnz={k}; raise max_nnz or "
            "pre-select features"
        )
    _scatter_ell(rows, cols, vals, counts, values_out, indices_out)


def from_scipy_like(rows, cols, vals, shape, max_nnz: int | None = None) -> EllFeatures:
    """Build EllFeatures from COO triplets (host-side, vectorized numpy).

    Duplicate (row, col) entries are coalesced by summation (scipy COO
    semantics) so the squared-value map ``rmatvec_sq`` stays consistent with
    the linear maps. Raises if any row exceeds ``max_nnz`` after coalescing —
    silent truncation would train a wrong model.
    """
    values, indices = pack_ell_host(rows, cols, vals, shape, max_nnz)
    values, indices = upload(
        "features", lambda: (jnp.asarray(values), jnp.asarray(indices))
    )
    return EllFeatures(values=values, indices=indices, num_cols=int(shape[1]))
