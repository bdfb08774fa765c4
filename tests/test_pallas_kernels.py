"""Pallas fused-kernel tests (interpret mode on the CPU mesh): the kernel
must match the XLA objective bit-for-bit-ish (f32 tolerances), including the
normalization-shift coefficient sum, padding no-ops, and vmap batching (the
per-entity random-effect inner loop). Its compilation for the TPU is
checked in test_tpu_compile.py."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from photon_ml_tpu.losses.pointwise import LogisticLoss, PoissonLoss, SquaredLoss
from photon_ml_tpu.ops.pallas_kernels import fused_value_grad_single

_LOSS = {"logistic": LogisticLoss, "squared": SquaredLoss, "poisson": PoissonLoss}


def _reference(kind, X, y, off, wt, w):
    z = X @ w + off
    if kind == "logistic":
        l = np.logaddexp(0, z) - y * z
        d1 = 1 / (1 + np.exp(-z)) - y
    elif kind == "squared":
        l = 0.5 * (z - y) ** 2
        d1 = z - y
    else:
        l = np.exp(z) - y * z
        d1 = np.exp(z) - y
    lw = np.where(wt > 0, wt * l, 0.0)
    dz = np.where(wt > 0, wt * d1, 0.0)
    return lw.sum(), dz @ X, dz.sum()


@pytest.mark.parametrize("kind", ["logistic", "squared", "poisson"])
def test_fused_matches_reference(rng, kind):
    n, d = 50, 13
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = (0.3 * rng.normal(size=d)).astype(np.float32)
    off = (0.1 * rng.normal(size=n)).astype(np.float32)
    wt = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    wt[::7] = 0.0  # padding-style rows
    if kind == "logistic":
        y = (rng.random(n) > 0.5).astype(np.float32)
    elif kind == "poisson":
        y = rng.poisson(1.0, size=n).astype(np.float32)
    else:
        y = rng.normal(size=n).astype(np.float32)

    val, grad, csum = fused_value_grad_single(
        X, y, off, wt, w, kind=_LOSS[kind], interpret=True
    )
    rv, rg, rc = _reference(kind, X, y, off, wt, w)
    assert float(val) == pytest.approx(rv, rel=2e-4)
    np.testing.assert_allclose(np.asarray(grad), rg, rtol=2e-3, atol=2e-3)
    assert float(csum) == pytest.approx(rc, rel=2e-3, abs=2e-3)


def test_single_kernel_vmaps(rng):
    """vmap over entities — the RE inner-loop batching pattern."""
    E, s, d = 6, 24, 10
    X = rng.normal(size=(E, s, d)).astype(np.float32)
    w = (0.2 * rng.normal(size=(E, d))).astype(np.float32)
    y = (rng.random((E, s)) > 0.5).astype(np.float32)
    off = np.zeros((E, s), dtype=np.float32)
    wt = np.ones((E, s), dtype=np.float32)

    batched = jax.vmap(
        lambda Xi, yi, oi, wti, wi: fused_value_grad_single(
            Xi, yi, oi, wti, wi, kind=LogisticLoss, interpret=True
        )
    )
    vals, grads, csums = batched(X, y, off, wt, w)
    assert vals.shape == (E,)
    assert grads.shape == (E, d)
    for e in range(E):
        rv, rg, _ = _reference("logistic", X[e], y[e], off[e], wt[e], w[e])
        assert float(vals[e]) == pytest.approx(rv, rel=2e-4)
        np.testing.assert_allclose(np.asarray(grads[e]), rg, rtol=2e-3, atol=2e-3)


def test_objective_uses_xla_when_disabled(rng):
    """With the env flag unset, the objective must not route into pallas."""
    from photon_ml_tpu.losses.objective import make_glm_objective
    from photon_ml_tpu.losses.pointwise import LogisticLoss
    from photon_ml_tpu.ops.data import LabeledData
    from photon_ml_tpu.ops.features import DenseFeatures

    n, d = 40, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    objective = make_glm_objective(LogisticLoss)
    v, g = objective.value_and_grad(jnp.zeros(d), data, jnp.float32(0.0))
    rv, rg, _ = _reference("logistic", X, y, np.zeros(n, np.float32),
                           np.ones(n, np.float32), np.zeros(d, np.float32))
    assert float(v) == pytest.approx(rv, rel=1e-4)
    np.testing.assert_allclose(np.asarray(g), rg, rtol=1e-3, atol=1e-3)
