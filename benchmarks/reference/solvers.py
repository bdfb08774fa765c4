"""Plain solvers for the reference: float32 ``jax.numpy``, no kernels, no
import of the program. They solve each convex block problem to its optimum
(as far as float32 lets them), so the reference is the model that block
coordinate descent with exact block solves produces; the program's capped,
toleranced solves are held against that.

``precision`` is "float32" (products in float32, matmuls at ``highest``) or
"bfloat16": the control of "How correct is decided", in which every product
of a feature value with a coefficient or a row factor takes bfloat16
operands, sums staying in float32. It is the step a later PR would be
tempted by (a bfloat16 payload in the routed maps, default-precision
matmuls in the random-effect solve).
"""

from __future__ import annotations

from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16")


def operand(x: jax.Array, precision: str) -> jax.Array:
    """``x`` as an operand of a product at ``precision``."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16)
    raise ValueError(f"unknown precision {precision!r}; have {PRECISIONS}")


class SparseRows:
    """X as [n, k] column ids and values; matvec by gather, rmatvec by
    scatter-add, both in row blocks so a block's [rows, k] temporaries stay
    small beside the vectors."""

    def __init__(self, cols: np.ndarray, vals: np.ndarray, n_cols: int,
                 precision: str = "float32", row_block: int = 1 << 18):
        self.n, self.k = cols.shape
        self.n_cols = int(n_cols)
        self.precision = precision
        self.blocks: List[Tuple[jax.Array, jax.Array]] = []
        for a in range(0, self.n, row_block):
            self.blocks.append((
                jnp.asarray(cols[a:a + row_block], dtype=jnp.int32),
                operand(jnp.asarray(vals[a:a + row_block]), precision),
            ))
        p = precision

        @jax.jit
        def _matvec_block(cols, vals, w):
            prod = vals * operand(w, p)[cols]
            return prod.astype(jnp.float32).sum(-1)

        @jax.jit
        def _rmatvec_block(g, cols, vals, c):
            prod = (vals * operand(c, p)[:, None]).astype(jnp.float32)
            return g.at[cols.reshape(-1)].add(prod.reshape(-1))

        self._matvec_block, self._rmatvec_block = _matvec_block, _rmatvec_block

    def matvec(self, w: jax.Array) -> jax.Array:
        return jnp.concatenate(
            [self._matvec_block(c, v, w) for c, v in self.blocks]
        )

    def rmatvec(self, c: jax.Array) -> jax.Array:
        g = jnp.zeros((self.n_cols,), jnp.float32)
        a = 0
        for cols, vals in self.blocks:
            g = self._rmatvec_block(g, cols, vals, c[a:a + cols.shape[0]])
            a += cols.shape[0]
        return g


def logistic_value(z, y):
    return jnp.logaddexp(0.0, z) - y * z


def logistic_d1(z, y):
    return jax.nn.sigmoid(z) - y


def poisson_value(z, y):
    return jnp.exp(z) - y * z


def poisson_d1(z, y):
    return jnp.exp(z) - y


LOSSES = {
    "LOGISTIC_REGRESSION": (logistic_value, logistic_d1),
    "POISSON_REGRESSION": (poisson_value, poisson_d1),
}


_vdot = jax.jit(jnp.vdot)
_axpy = jax.jit(lambda a, x, y: a * x + y)


def _two_loop(q: jax.Array, pairs) -> jax.Array:
    """L-BFGS two-loop recursion: the inverse-Hessian estimate of the
    (s, y, 1/s.y) ``pairs``, oldest first, applied to ``q``."""
    alphas = []
    for s, y, rho in reversed(pairs):
        a = rho * float(_vdot(s, q))
        alphas.append(a)
        q = _axpy(-a, y, q)
    if pairs:
        s, y, _ = pairs[-1]
        q = q * (float(_vdot(s, y)) / float(_vdot(y, y)))
    for (s, y, rho), a in zip(pairs, reversed(alphas)):
        b = rho * float(_vdot(y, q))
        q = _axpy(a - b, s, q)
    return q


def minimize_lbfgs(
    value_and_grad: Callable[[jax.Array], Tuple[jax.Array, jax.Array]],
    w0: jax.Array,
    history: int = 10,
    max_iterations: int = 100,
    gradient_tolerance: float = 1e-5,
    gradient_scale: float = 0.0,
) -> Tuple[jax.Array, dict]:
    """Textbook L-BFGS (two-loop recursion, Armijo backtracking), driven
    from the host one evaluation at a time. Stops when the gradient norm has
    fallen by ``gradient_tolerance`` against the larger of the start's and
    ``gradient_scale`` (a warm start begins near the optimum), when two
    iterations in a row lower the value by less than float32 resolves, or
    when no lower value is found along the direction."""
    vdot, axpy = _vdot, _axpy
    w = w0
    f, g = value_and_grad(w)
    f = float(f)
    g0_norm = max(float(jnp.linalg.norm(g)), gradient_scale)
    flat = 0
    pairs: List[Tuple[jax.Array, jax.Array, float]] = []
    evaluations, it = 1, 0
    for it in range(1, max_iterations + 1):
        g_norm = float(jnp.linalg.norm(g))
        if g_norm <= gradient_tolerance * max(g0_norm, 1e-30):
            it -= 1
            break
        q = _two_loop(g, pairs)
        d = -q
        slope = float(vdot(g, d))
        if slope >= 0:
            d, slope = -g, -g_norm * g_norm
        t = 1.0 if pairs else 1.0 / max(g_norm, 1e-12)
        moved = False
        for _ in range(12):
            w_t = axpy(t, d, w)
            f_t, g_t = value_and_grad(w_t)
            evaluations += 1
            f_t = float(f_t)
            if np.isfinite(f_t) and f_t <= f + 1e-4 * t * slope:
                moved = True
                break
            t *= 0.5
        if not moved:
            it -= 1
            break
        s, y = w_t - w, g_t - g
        sy = float(vdot(s, y))
        if sy > 1e-20:
            pairs.append((s, y, 1.0 / sy))
            pairs = pairs[-history:]
        flat = flat + 1 if f - f_t <= 2e-7 * abs(f) else 0
        w, f, g = w_t, f_t, g_t
        if flat >= 2:
            break
    return w, {
        "iterations": it,
        "evaluations": evaluations,
        "value": f,
        "gradient_norm": float(jnp.linalg.norm(g)),
        "gradient_norm_start": g0_norm,
    }


def solve_entities_newton(
    x: jax.Array,        # [E, S, D]
    labels: jax.Array,   # [E, S]
    offsets: jax.Array,  # [E, S]
    mask: jax.Array,     # [E, S] 1 for a real sample
    theta0: jax.Array,   # [E, D]
    l2: float,
    precision: str = "float32",
    iterations: int = 30,
) -> jax.Array:
    """Every entity's L2-regularised logistic fit at once, by damped Newton:
    D is small, so the Hessian is formed and solved outright."""
    return _newton(x, labels, offsets, mask, theta0, jnp.float32(l2),
                   precision, iterations)


def _entity_margins(x, theta, precision):
    with jax.default_matmul_precision(
        "highest" if precision == "float32" else "default"
    ):
        return jnp.einsum(
            "esd,ed->es", operand(x, precision), operand(theta, precision),
            preferred_element_type=jnp.float32,
        )


def _newton(x, labels, offsets, mask, theta0, l2, precision, iterations):
    @jax.jit
    def run(x, labels, offsets, mask, theta0, l2):
        xo = operand(x, precision)
        mm = "highest" if precision == "float32" else "default"

        def value(theta):
            z = _entity_margins(x, theta, precision) + offsets
            return (mask * logistic_value(z, labels)).sum(-1) + 0.5 * l2 * (theta * theta).sum(-1)

        def body(_, theta):
            z = _entity_margins(x, theta, precision) + offsets
            s = jax.nn.sigmoid(z)
            d1 = mask * (s - labels)
            d2 = mask * s * (1.0 - s)
            with jax.default_matmul_precision(mm):
                grad = jnp.einsum("esd,es->ed", xo, operand(d1, precision),
                                  preferred_element_type=jnp.float32) + l2 * theta
                # weight one operand first: a three-way einsum may build
                # the [E, S, D, D] outer products
                hess = jnp.einsum("esd,esf->edf",
                                  operand(x * d2[..., None], precision), xo,
                                  preferred_element_type=jnp.float32)
            hess = hess + l2 * jnp.eye(theta.shape[-1], dtype=jnp.float32)
            with jax.default_matmul_precision("highest"):
                step = jnp.linalg.solve(hess, grad[..., None])[..., 0]
            f0 = value(theta)

            def halve(_, carry):
                t, best = carry
                ok = value(theta - t[:, None] * step) <= f0
                return jnp.where(ok, t, 0.5 * t), best

            t, _ = jax.lax.fori_loop(0, 8, halve, (jnp.ones_like(f0), f0))
            cand = theta - t[:, None] * step
            return jnp.where((value(cand) <= f0)[:, None], cand, theta)

        return jax.lax.fori_loop(0, iterations, body, theta0)

    return run(x, labels, offsets, mask, theta0, l2)


def auc(scores: np.ndarray, labels: np.ndarray) -> float:
    """Rank-sum AUC with tied scores sharing their mean rank, in float64."""
    scores = np.asarray(scores, dtype=np.float64)
    pos = np.asarray(labels) > 0.5
    order = np.argsort(scores, kind="stable")
    sorted_scores = scores[order]
    ranks = np.empty(scores.size, dtype=np.float64)
    boundaries = np.flatnonzero(np.diff(sorted_scores)) + 1
    starts = np.concatenate([[0], boundaries])
    ends = np.concatenate([boundaries, [scores.size]])
    mean_rank = (starts + ends + 1) / 2.0  # ranks are 1-based
    ranks[order] = np.repeat(mean_rank, ends - starts)
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg))


def pseudo_gradient(w, g, l1, lo=None, hi=None):
    """The steepest-descent direction's negative for f + l1*|w|_1 on the box
    [lo, hi]: the minimum-norm subgradient, with components that point out
    of the box at an active bound set to nought."""
    right, left = g + l1, g - l1
    pg = jnp.where(w > 0, right, jnp.where(w < 0, left,
         jnp.where(right < 0, right, jnp.where(left > 0, left, 0.0))))
    if lo is not None:
        pg = jnp.where((w <= lo) & (pg > 0), 0.0, pg)
    if hi is not None:
        pg = jnp.where((w >= hi) & (pg < 0), 0.0, pg)
    return pg


def minimize_owlqn(
    value_and_grad: Callable[[jax.Array], Tuple[jax.Array, jax.Array]],
    w0: jax.Array,
    l1: float,
    lo: float = None,
    hi: float = None,
    history: int = 10,
    max_iterations: int = 200,
    gradient_tolerance: float = 1e-5,
) -> Tuple[jax.Array, dict]:
    """Orthant-wise L-BFGS (Andrew and Gao, 2007) for a smooth convex f plus
    l1*|w|_1 on a box, driven from the host: pseudo-gradient, two-loop
    direction from the smooth part's curvature pairs, the direction kept to
    the pseudo-gradient's descent orthant, and a backtracking search over
    points projected onto the orthant and the box."""
    vdot, axpy = _vdot, _axpy
    pgrad = jax.jit(lambda w, g: pseudo_gradient(w, g, l1, lo, hi))

    @jax.jit
    def project(w, d, pg, t):
        xi = jnp.where(w != 0, jnp.sign(w), jnp.sign(-pg))
        w_t = w + t * d
        w_t = jnp.where(jnp.sign(w_t) == xi, w_t, 0.0)
        if lo is not None or hi is not None:
            w_t = jnp.clip(w_t, lo, hi)
        return w_t

    full = jax.jit(lambda f, w: f + l1 * jnp.sum(jnp.abs(w)))
    w = w0
    f, g = value_and_grad(w)
    F = float(full(f, w))
    pg = pgrad(w, g)
    pg0_norm = float(jnp.linalg.norm(pg))
    pairs: List[Tuple[jax.Array, jax.Array, float]] = []
    evaluations, it, flat = 1, 0, 0
    for it in range(1, max_iterations + 1):
        pg_norm = float(jnp.linalg.norm(pg))
        if pg_norm <= gradient_tolerance * max(pg0_norm, 1e-30):
            it -= 1
            break
        q = _two_loop(pg, pairs)
        d = jnp.where(q * pg > 0, -q, 0.0)  # stay in the descent orthant
        if float(vdot(d, pg)) >= 0:
            d = -pg
        t = 1.0 if pairs else 1.0 / max(pg_norm, 1e-12)
        moved = False
        for _ in range(20):
            w_t = project(w, d, pg, t)
            f_t, g_t = value_and_grad(w_t)
            evaluations += 1
            F_t = float(full(f_t, w_t))
            if np.isfinite(F_t) and F_t <= F + 1e-4 * float(vdot(pg, w_t - w)):
                moved = True
                break
            t *= 0.5
        if not moved:
            it -= 1
            break
        s, y = w_t - w, g_t - g
        sy = float(vdot(s, y))
        if sy > 1e-20:
            pairs.append((s, y, 1.0 / sy))
            pairs = pairs[-history:]
        flat = flat + 1 if F - F_t <= 2e-7 * abs(F) else 0
        w, F, g = w_t, F_t, g_t
        pg = pgrad(w, g)
        if flat >= 2:
            break
    return w, {
        "iterations": it,
        "evaluations": evaluations,
        "value": F,
        "pseudo_gradient_norm": float(jnp.linalg.norm(pg)),
        "pseudo_gradient_norm_start": pg0_norm,
    }
