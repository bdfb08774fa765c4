"""The GLM objective: value / gradient / Hessian-vector / Hessian-diagonal.

Reference parity: this is the fusion of function/glm/{ValueAndGradient,
HessianVector,HessianDiagonal}Aggregator.scala (the per-partition compute
kernels) with function/L2Regularization.scala (stackable L2 term) and
DistributedGLMLossFunction.scala / SingleNodeGLMLossFunction.scala (the
distributed/local bindings). On TPU there is no distributed/local split at
this layer: the same jit-compiled functions run on one chip, inside ``vmap``
for per-entity solves, or inside ``shard_map`` with a ``psum`` over the batch
axis for the sharded fixed effect (dist/sharded_objective.py).

Semantics (matching the reference exactly):
- objective(w) = sum_i weight_i * l(z_i, y_i) + 0.5 * l2 * ||w||^2
- z_i = x_i . (factor .* w) - shift . (factor .* w) + offset_i
- L1 is NOT part of the smooth objective; OWL-QN handles it at the optimizer
  level (reference OWLQN.scala:40).

``l2_weight`` is a traced scalar argument so λ sweeps reuse one compiled
program (reference updateRegularizationWeight,
DistributedOptimizationProblem.scala:60-71).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple, Type

import jax
import jax.numpy as jnp

from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.losses.pointwise import PointwiseLoss
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.ops.features import DenseFeatures

_IDENTITY_NORM = NormalizationContext()


def _norm_of(data: LabeledData) -> NormalizationContext:
    return data.norm if data.norm is not None else _IDENTITY_NORM


class GlmObjective(NamedTuple):
    """Bundle of pure functions; pass as a static closure into optimizers.

    The NormalizationContext is read from ``data.norm`` so that factor/shift
    arrays are traced jit arguments, not compile-time constants.
    """

    value: "callable"          # (w, data, l2) -> scalar
    value_and_grad: "callable"  # (w, data, l2) -> (scalar, [d])
    hessian_vec: "callable"    # (w, v, data, l2) -> [d]
    hessian_diag: "callable"   # (w, data, l2) -> [d]
    has_hessian: bool


def make_glm_objective(
    loss: Type[PointwiseLoss], use_pallas: bool = None
) -> GlmObjective:
    """``use_pallas``: route eligible dense problems through the fused
    pallas kernel; None (default) defers to the PHOTON_ML_TPU_PALLAS flag
    (ops/pallas_kernels.enabled), read at every call.

    One objective per (loss, resolved flag) for the life of the process: the
    jitted programs built over it key on the identity of its functions, so
    equal calls have to return the same closures for a program to be reused
    (estimators/model_training.py, streaming/solver.py)."""
    if use_pallas is None:
        from photon_ml_tpu.ops import pallas_kernels

        use_pallas = pallas_kernels.enabled()
    return _glm_objective(loss, bool(use_pallas))


# unbounded: one entry per loss class and flag
@functools.lru_cache(maxsize=None)
def _glm_objective(loss: Type[PointwiseLoss], use_pallas: bool) -> GlmObjective:
    def margins(w: jax.Array, data: LabeledData) -> jax.Array:
        norm = _norm_of(data)
        ew = norm.effective_coefficients(w)
        return data.features.matvec(ew) - norm.margin_shift(ew) + data.offsets

    def _wmask(weights: jax.Array, terms: jax.Array) -> jax.Array:
        # weight-0 padding rows must be exact no-ops even when the unweighted
        # term overflows to inf (0 * inf = NaN would poison the sum)
        return jnp.where(weights > 0, weights * terms, 0.0)

    def value(w: jax.Array, data: LabeledData, l2: jax.Array) -> jax.Array:
        z = margins(w, data)
        loss_sum = jnp.sum(_wmask(data.weights, loss.value(z, data.labels)))
        return loss_sum + 0.5 * l2 * jnp.dot(w, w)

    def value_and_grad(
        w: jax.Array, data: LabeledData, l2: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        norm = _norm_of(data)
        if (
            use_pallas
            and isinstance(data.features, DenseFeatures)
            and data.features.matrix.ndim == 2
            and norm.is_identity
        ):
            # fused MXU kernel: one HBM pass over X for value + gradient
            # (None => problem too large for one VMEM block; use XLA)
            from photon_ml_tpu.ops.pallas_kernels import fused_value_grad_auto

            fused = fused_value_grad_auto(
                data.features.matrix, data.labels, data.offsets,
                data.weights, w, kind=loss,
            )
            if fused is not None:
                loss_sum, raw, _ = fused
                return loss_sum + 0.5 * l2 * jnp.dot(w, w), raw + l2 * w
        z = margins(w, data)
        loss_sum = jnp.sum(_wmask(data.weights, loss.value(z, data.labels)))
        c = _wmask(data.weights, loss.d1(z, data.labels))
        raw = data.features.rmatvec(c)
        grad = norm.apply_to_gradient(raw, jnp.sum(c))
        return loss_sum + 0.5 * l2 * jnp.dot(w, w), grad + l2 * w

    def hessian_vec(
        w: jax.Array, v: jax.Array, data: LabeledData, l2: jax.Array
    ) -> jax.Array:
        """Gauss-Newton/true Hessian-vector product via the analytic d2z form
        (reference HessianVectorAggregator.scala:36): Hv = J^T diag(w_i d2_i) J v
        where J is the normalized feature map."""
        norm = _norm_of(data)
        z = margins(w, data)
        ev = norm.effective_coefficients(v)
        zv = data.features.matvec(ev) - norm.margin_shift(ev)
        c2 = _wmask(data.weights, loss.d2(z, data.labels) * zv)
        raw = data.features.rmatvec(c2)
        return norm.apply_to_gradient(raw, jnp.sum(c2)) + l2 * v

    def hessian_diag(w: jax.Array, data: LabeledData, l2: jax.Array) -> jax.Array:
        """diag(H)_j = sum_i a_i * ((x_ij - s_j) f_j)^2 + l2, a_i = weight_i*d2_i
        (reference HessianDiagonalAggregator.scala:33; used for coefficient
        variances, DistributedOptimizationProblem.scala:80-94).

        Expanded so sparse layouts never densify:
        sum a (x-s)^2 = (X*X)^T a - 2 s * (X^T a) + s^2 * sum(a).
        """
        norm = _norm_of(data)
        z = margins(w, data)
        a = _wmask(data.weights, loss.d2(z, data.labels))
        sq = data.features.rmatvec_sq(a)
        if norm.shift is not None:
            lin = data.features.rmatvec(a)
            sq = sq - 2.0 * norm.shift * lin + norm.shift * norm.shift * jnp.sum(a)
        if norm.factor is not None:
            sq = sq * norm.factor * norm.factor
        return sq + l2

    return GlmObjective(
        value=value,
        value_and_grad=value_and_grad,
        hessian_vec=hessian_vec,
        hessian_diag=hessian_diag,
        has_hessian=loss.has_hessian,
    )
