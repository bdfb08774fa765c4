"""Pallas TPU kernels for the GLM hot path.

The reference's per-partition compute kernel (ValueAndGradientAggregator
.scala:33: one pass accumulating Σ w·l(z,y) and Σ w·l′·x) maps to TPU as a
fused MXU kernel: z = X·w rides the MXU, the pointwise loss and its
derivative ride the VPU, and gradᵀ = dzᵀ·X rides the MXU again — ONE pass
over X in HBM instead of the two XLA makes for matvec + rmatvec.

Dense blocks only (the TPU has no efficient arbitrary gather/scatter, so the
ELL sparse path stays on XLA; per-entity random-effect blocks are dense by
construction via index-map projection). The whole problem is one VMEM block,
so ``jax.vmap`` batches the kernel over entities.

See /opt/skills/guides/pallas_guide.md for the programming model.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _loss_terms(kind, z, y):
    """(l(z,y), dl/dz) on the VPU. ``kind`` is a PointwiseLoss class (its
    value/d1 are pure elementwise jnp, valid inside a kernel) — one source
    of truth with the XLA objective."""
    return kind.value(z, y), kind.d1(z, y)


def _pad_to(a: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = a.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, rem)
    return jnp.pad(a, pad)


def _single_kernel(kind: str, x_ref, y_ref, off_ref, wt_ref, w_ref,
                   val_ref, grad_ref, csum_ref):
    """Whole problem in one VMEM block, no grid: jax.vmap batches it cleanly
    (the batch axis becomes the grid) — the per-entity random-effect
    inner-loop kernel."""
    x = x_ref[...]                       # [S, D]
    z = jax.lax.dot_general(
        x, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )[:, 0] + off_ref[0, :]
    y = y_ref[0, :]
    wt = wt_ref[0, :]
    l, d1 = _loss_terms(kind, z, y)
    lw = jnp.where(wt > 0, wt * l, 0.0)
    dz = jnp.where(wt > 0, wt * d1, 0.0)
    # Mosaic forbids scalar stores to VMEM: store (1,1)-shaped arrays
    val_ref[...] = jnp.sum(lw)[None, None]
    csum_ref[...] = jnp.sum(dz)[None, None]
    grad_ref[...] = jax.lax.dot_general(
        dz[None, :], x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def fused_value_grad_single(
    matrix: jax.Array,    # [s, d]
    labels: jax.Array,    # [s]
    offsets: jax.Array,   # [s]
    weights: jax.Array,   # [s]
    w: jax.Array,         # [d]
    kind=None,  # PointwiseLoss class (static); required
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-block fused pass; vmap-safe (use for per-entity solves)."""
    if kind is None:
        raise ValueError("kind (a PointwiseLoss class) is required")
    s, d = matrix.shape
    x = _pad_to(_pad_to(matrix, 0, 8), 1, LANE)
    sp, dp = x.shape
    yv = _pad_to(labels.astype(jnp.float32)[None, :], 1, 8)
    off = _pad_to(offsets.astype(jnp.float32)[None, :], 1, 8)
    wt = _pad_to(weights.astype(jnp.float32)[None, :], 1, 8)
    wv = _pad_to(w.astype(jnp.float32)[None, :], 1, LANE)
    val, grad, csum = pl.pallas_call(
        functools.partial(_single_kernel, kind),
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, yv, off, wt, wv)
    return val[0, 0], grad[0, :d], csum[0, 0]


# At most this many elements go through the single-block kernel (must fit
# VMEM comfortably); larger dense problems stay on XLA.
SINGLE_BLOCK_MAX_ELEMENTS = 2_000_000


def fused_value_grad_auto(matrix, labels, offsets, weights, w, kind):
    """The objective's entry: problems too large for one VMEM block return
    None and the caller stays on XLA, which GSPMD can partition
    (pallas_call has no partitioning rule, so routing a mesh-sharded FE
    matrix here would replicate it)."""
    s, d = matrix.shape
    if s * d > SINGLE_BLOCK_MAX_ELEMENTS:
        return None
    return fused_value_grad_single(
        matrix, labels, offsets, weights, w, kind=kind
    )


def pallas_available() -> bool:
    """True on a TPU backend: the only place the kernels run outside the
    tests' interpret mode. A kernel Mosaic refuses there is an error, never
    a reason to take another path."""
    return jax.default_backend() == "tpu"


@functools.cache
def enabled() -> bool:
    """The fused RE kernel is opt-in: PHOTON_ML_TPU_PALLAS=1 enables it on a
    TPU backend. The objective checks this once at trace time."""
    import os

    return os.environ.get("PHOTON_ML_TPU_PALLAS", "") == "1" and pallas_available()
