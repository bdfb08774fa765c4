"""Plain reference for the full GAME ratings model: exact block coordinate
descent for the squared loss over a sparse fixed effect, per-entity random
effects and a user x item factored coordinate, with the training objective
and the held-out RMSE read after every block update, as the program's driver
reads them.

Float32 ``jax.numpy`` at ``highest`` matmul precision, gather and scatter-add
in row blocks. What it takes: the seeded rows (datagen.Rows) and the numbers
of the configuration file. Nothing of the program: no routed plan, no bucket,
no Kronecker features, no model class. It runs after the window has closed
and the program's state is freed.

The blocks, each solved to its optimum:

- fixed effect: ``0.5 sum (x.w + offset - y)^2 + 0.5 l2 |w|^2`` by the
  host-driven L-BFGS of ``solvers.py``;
- a random effect: every entity's ridge problem at once, by its normal
  equations in its ``dim`` dimensions (the kept rows of a capped entity are
  ``glmix._EntityBlocks``');
- the factored coordinate, ``score = x . (B v_user)`` with ``x`` the row's
  item id one-hot, so ``score = B[item] . v_user``: ``alternations`` times
  (a) every user's ridge problem for ``v_user`` in the ``k`` dimensions of
  the current ``B``, by its normal equations, and (b) the ridge problem for
  ``vec(B)``, by conjugate gradients on its normal equations, every product
  computed row by row (a gather of the row's item factors and user factors,
  a scatter-add of the row's outer product into the item's row of the
  gradient). ``B`` starts at ``default_rng(seed).standard_normal((items, k))
  / sqrt(k)`` and ``v`` at zero; both carry over to the next outer iteration.

Departures from FactoredRandomEffectCoordinate.scala, each because this is
the plain side:

- the block solves are exact where upstream (:112-146) runs its configured,
  capped optimizers: the latent solves are normal equations and not L-BFGS
  over vmapped entities, the matrix solve (:227-280) is CG on a quadratic
  and not an L-BFGS GLM solve over materialized Kronecker features
  ``kron(x, v)``; the Kronecker product is never formed, nor any stand-in
  for it;
- CG is preconditioned by the diagonal of its operator (an item's rows
  differ by four orders of magnitude under Zipf popularity) and, because its
  recurrence drifts from the true gradient in float32, starts again from the
  gradient computed anew until that meets the tolerance or stops improving
  (as ``reference/linear.py`` does);
- the projection matrix is seeded by numpy's generator, where upstream draws
  a Gaussian matrix from Spark's (:95); the program's seed and formula;
- the warm start of step (a) is of no account (the solve is exact); step
  (b) starts from the current ``B``.

``precision`` "bfloat16" is the control: every product of a feature value or
a factor with a coefficient or a factor takes bfloat16 operands, sums staying
in float32; the small linear systems are solved at ``highest`` either way.
``frozen_matrix=True`` is a stand-in: step (b) is left out, ``B`` stays where
it started.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import solvers
from benchmarks.reference.glmix import _EntityBlocks


# the factored coordinate: its block in the configuration file and its name
# in the update order
MF = "user_item_mf"


@dataclasses.dataclass
class Snapshot:
    """The model after one block update, with what was read there."""

    step: int
    coordinate: str
    objective: float
    rmse: Optional[float]
    fixed: Optional[jax.Array]        # [n_cols]
    random: Dict[str, jax.Array]      # name -> [n_entities, dim]
    latent: Optional[jax.Array]       # [n_users, k]
    matrix: Optional[jax.Array]       # [n_items, k]

    def leaves(self) -> Dict[str, np.ndarray]:
        out = {"fixed": self.fixed, **self.random, "latent": self.latent, "matrix": self.matrix}
        return {k: np.asarray(v) for k, v in out.items() if v is not None}


def _mm(precision: str) -> str:
    return "highest" if precision == "float32" else "default"


def _ridge(x, labels, offsets, mask, l2, precision):
    """Every entity's ``argmin 0.5 sum mask (x.t + offset - y)^2 + 0.5 l2
    |t|^2`` at once: ``x`` [E, S, D], the rest [E, S]."""
    @jax.jit
    def run(x, labels, offsets, mask, l2):
        xo = solvers.operand(x, precision)
        with jax.default_matmul_precision(_mm(precision)):
            gram = jnp.einsum("esd,esf->edf", solvers.operand(x * mask[..., None], precision),
                              xo, preferred_element_type=jnp.float32)
            rhs = jnp.einsum("esd,es->ed", xo,
                             solvers.operand(mask * (labels - offsets), precision),
                             preferred_element_type=jnp.float32)
        gram = gram + l2 * jnp.eye(x.shape[-1], dtype=jnp.float32)
        with jax.default_matmul_precision("highest"):
            return jnp.linalg.solve(gram, rhs[..., None])[..., 0]

    return run(x, labels, offsets, mask, jnp.float32(l2))


class _FactorRows:
    """The factored coordinate's rows: (user, item) of every row, in row
    blocks. ``scores`` gathers, ``gradient`` scatter-adds."""

    def __init__(self, users: np.ndarray, items: np.ndarray, n_items: int,
                 precision: str, row_block: int = 1 << 18):
        self.n, self.n_items, p = users.shape[0], int(n_items), precision
        self.blocks = [
            (jnp.asarray(users[a:a + row_block], dtype=jnp.int32),
             jnp.asarray(items[a:a + row_block], dtype=jnp.int32))
            for a in range(0, self.n, row_block)
        ]

        @jax.jit
        def _scores(users, items, B, V):
            prod = solvers.operand(B, p)[items] * solvers.operand(V, p)[users]
            return prod.astype(jnp.float32).sum(-1)

        @jax.jit
        def _gradient(G, users, items, V, c):
            prod = solvers.operand(c, p)[:, None] * solvers.operand(V, p)[users]
            return G.at[items].add(prod.astype(jnp.float32))

        @jax.jit
        def _diagonal(D, users, items, V):
            v = solvers.operand(V, p)[users]
            return D.at[items].add((v * v).astype(jnp.float32))

        self._scores, self._gradient, self._diagonal = _scores, _gradient, _diagonal

    def scores(self, B, V):
        """[n]: ``B[item] . V[user]`` of every row."""
        return jnp.concatenate([self._scores(u, i, B, V) for u, i in self.blocks])

    def gradient(self, V, c):
        """[n_items, k]: sum over rows of ``c_row * V[user_row]`` into the
        row's item."""
        G = jnp.zeros((self.n_items, V.shape[1]), jnp.float32)
        a = 0
        for u, i in self.blocks:
            G = self._gradient(G, u, i, V, c[a:a + u.shape[0]])
            a += u.shape[0]
        return G

    def diagonal(self, V):
        """[n_items, k]: the diagonal of ``B -> gradient(V, scores(B, V))``."""
        D = jnp.zeros((self.n_items, V.shape[1]), jnp.float32)
        for u, i in self.blocks:
            D = self._diagonal(D, u, i, V)
        return D


class GameMfReference:
    def __init__(self, config: dict, problem, seed: int, precision: str = "float32"):
        if config["task"] != "LINEAR_REGRESSION":
            raise ValueError("the GAME ratings reference is written for the squared loss")
        self.config, self.precision = config, precision
        self.order: List[str] = list(config["update_order"])
        train, held = problem.train, problem.held_out
        self.n_cols = problem.n_cols
        self.features = solvers.SparseRows(train.cols, train.vals, problem.n_cols, precision)
        self.labels = jnp.asarray(train.labels)
        self.fe_l2 = float(config["fixed_effect"]["regularization_weight"])
        self.fe_gradient_scale = 0.0
        self.re = config["random_effects"]
        self.ids = {k: jnp.asarray(v, dtype=jnp.int32) for k, v in train.entities.items()}
        self.x = {k: jnp.asarray(v) for k, v in train.entity_x.items()}
        self.blocks = {
            name: _EntityBlocks(
                train.entities[name], train.entity_x[name], train.labels,
                re["n_entities"], re.get("active_cap"), int(re.get("sample_seed", 0)),
            )
            for name, re in self.re.items()
        }
        mf = self.mf = config[MF]
        self.users, self.items = mf["entities"], mf["items"]
        self.n_users = int(self.re[self.users]["n_entities"])
        self.n_items = int(self.re[self.items]["n_entities"])
        self.k = int(mf["latent_factors"])
        self.seed = int(seed)
        self.rows = _FactorRows(train.entities[self.users], train.entities[self.items],
                                self.n_items, precision)
        # a user's rows, padded to the longest: positions into the row order
        self.user_blocks = _EntityBlocks(
            train.entities[self.users], np.zeros((train.n, 1), np.float32), train.labels,
            self.n_users, None, 0,
        )
        self.user_items = jnp.asarray(train.entities[self.items], dtype=jnp.int32)[self.user_blocks.pos]
        self.held = held
        self.held_features = solvers.SparseRows(held.cols, held.vals, problem.n_cols, precision)
        self.held_ids = {k: jnp.asarray(v, dtype=jnp.int32) for k, v in held.entities.items()}
        self.held_x = {k: jnp.asarray(v) for k, v in held.entity_x.items()}
        self.held_rows = _FactorRows(held.entities[self.users], held.entities[self.items],
                                     self.n_items, precision)

    def initial_matrix(self) -> jax.Array:
        B = np.random.default_rng(self.seed).standard_normal((self.n_items, self.k))
        return jnp.asarray((B / np.sqrt(self.k)).astype(np.float32))

    # -- scores ---------------------------------------------------------
    def _entity_scores(self, x, ids, theta):
        p = self.precision
        prod = solvers.operand(x, p) * solvers.operand(theta, p)[ids]
        return prod.astype(jnp.float32).sum(-1)

    def train_scores(self, snap: Snapshot) -> Dict[str, jax.Array]:
        scores = {}
        if snap.fixed is not None:
            scores["fixed"] = self.features.matvec(snap.fixed)
        for name, theta in snap.random.items():
            scores[name] = self._entity_scores(self.x[name], self.ids[name], theta)
        if snap.matrix is not None:
            scores[MF] = self.rows.scores(snap.matrix, snap.latent)
        return scores

    def objective(self, total, fixed, random, latent, matrix) -> float:
        r = total - self.labels
        value = 0.5 * float(jnp.vdot(r, r))
        if fixed is not None:
            value += 0.5 * self.fe_l2 * float(jnp.vdot(fixed, fixed))
        for name, theta in random.items():
            value += 0.5 * float(self.re[name]["regularization_weight"]) * float(jnp.vdot(theta, theta))
        if matrix is not None:
            value += 0.5 * float(self.mf["latent"]["regularization_weight"]) * float(jnp.vdot(latent, latent))
            value += 0.5 * float(self.mf["matrix"]["regularization_weight"]) * float(jnp.vdot(matrix, matrix))
        return value

    def held_out_rmse(self, fixed, random, latent, matrix) -> float:
        z = jnp.zeros((self.held.n,), jnp.float32)
        if fixed is not None:
            z = z + self.held_features.matvec(fixed)
        for name, theta in random.items():
            z = z + self._entity_scores(self.held_x[name], self.held_ids[name], theta)
        if matrix is not None:
            z = z + self.held_rows.scores(matrix, latent)
        d = np.asarray(z, np.float64) - np.asarray(self.held.labels, np.float64)
        return float(np.sqrt(np.mean(d * d)))

    # -- block solves ---------------------------------------------------
    def _solve_fixed(self, w0, offsets):
        l2 = jnp.float32(self.fe_l2)
        labels, feats = self.labels, self.features

        @jax.jit
        def pointwise(z, w):
            r = z + offsets - labels
            return 0.5 * jnp.vdot(r, r) + 0.5 * l2 * jnp.vdot(w, w), r

        def value_and_grad(w):
            value, r = pointwise(feats.matvec(w), w)
            return value, feats.rmatvec(r) + l2 * w

        w, info = solvers.minimize_lbfgs(
            value_and_grad, w0, gradient_tolerance=1e-6,
            gradient_scale=self.fe_gradient_scale,
        )
        # the gradient at the zero model scales every later, warm-started solve
        self.fe_gradient_scale = max(self.fe_gradient_scale, info["gradient_norm_start"])
        return w, info

    def _solve_entities(self, name, offsets):
        b = self.blocks[name]
        return _ridge(b.x, b.labels, offsets[b.pos] * b.mask, b.mask,
                      float(self.re[name]["regularization_weight"]), self.precision)

    def _solve_latent(self, B, offsets):
        """Step (a): every user's factors in the space of ``B``."""
        ub = self.user_blocks
        gathered = B[self.user_items] * ub.mask[..., None]    # [U, S, k]
        return _ridge(gathered, ub.labels, offsets[ub.pos] * ub.mask, ub.mask,
                      float(self.mf["latent"]["regularization_weight"]), self.precision)

    def _solve_matrix(self, B0, V, offsets, tolerance: float = 1e-7, max_iterations: int = 2000):
        """Step (b): ``argmin_B 0.5 sum (B[item].V[user] + offset - y)^2 +
        0.5 l2 |B|^2`` by preconditioned CG from ``B0``."""
        l2 = jnp.float32(self.mf["matrix"]["regularization_weight"])
        rows, target = self.rows, self.labels - offsets
        vdot, axpy = solvers._vdot, solvers._axpy

        def gradient(B):
            return rows.gradient(V, rows.scores(B, V) - target) + l2 * B

        def hessian_vec(D):
            return rows.gradient(V, rows.scores(D, V)) + l2 * D

        inverse_diagonal = 1.0 / (rows.diagonal(V) + l2)
        B = B0
        g = gradient(B)
        g0_norm = float(jnp.linalg.norm(gradient(jnp.zeros_like(B))))
        relative, iterations, restarts = float(jnp.linalg.norm(g)) / max(g0_norm, 1e-30), 0, 0
        while relative > tolerance:
            r = -g
            z = inverse_diagonal * r
            d, rz = z, float(vdot(r, z))
            while iterations < max_iterations and rz > 0:
                hd = hessian_vec(d)
                alpha = rz / float(vdot(d, hd))
                B, r = axpy(alpha, d, B), axpy(-alpha, hd, r)
                iterations += 1
                if float(jnp.linalg.norm(r)) <= tolerance * g0_norm:
                    break
                z = inverse_diagonal * r
                rz_new = float(vdot(r, z))
                d, rz = axpy(rz_new / rz, d, z), rz_new
            g = gradient(B)
            before, relative = relative, float(jnp.linalg.norm(g)) / max(g0_norm, 1e-30)
            if iterations >= max_iterations or relative > 0.5 * before:
                break
            restarts += 1
        return B, {"iterations": iterations, "restarts": restarts, "relative_gradient": relative}

    # -- the descent ----------------------------------------------------
    def run(self, steps: int, log=None, start: Optional[Snapshot] = None,
            frozen_matrix: bool = False) -> List[Snapshot]:
        """``steps`` outer iterations from the zero model (``B`` from its
        seeded start), or from the model of ``start`` (a fit that is handed
        its predecessor's); a snapshot after every block update."""
        with jax.default_matmul_precision(_mm(self.precision)):
            return self._run(steps, log, start, frozen_matrix)

    def _run(self, steps, log, start, frozen_matrix) -> List[Snapshot]:
        fixed: Optional[jax.Array] = None
        random: Dict[str, jax.Array] = {}
        latent = matrix = None
        scores: Dict[str, jax.Array] = {}
        total = jnp.zeros_like(self.labels)
        if start is not None:
            fixed, random = start.fixed, dict(start.random)
            latent, matrix = start.latent, start.matrix
            scores = self.train_scores(start)
            total = sum(scores.values())
        out: List[Snapshot] = []
        for step in range(steps):
            for cid in self.order:
                own = scores.get(cid)
                residual = total - own if own is not None else total
                info = {}
                if cid == "fixed":
                    w0 = fixed if fixed is not None else jnp.zeros((self.n_cols,), jnp.float32)
                    fixed, info = self._solve_fixed(w0, residual)
                    new = self.features.matvec(fixed)
                elif cid in self.re:
                    random = dict(random)
                    random[cid] = self._solve_entities(cid, residual)
                    new = self._entity_scores(self.x[cid], self.ids[cid], random[cid])
                else:
                    if matrix is None:
                        matrix = self.initial_matrix()
                    for _ in range(int(self.mf["alternations"])):
                        latent = self._solve_latent(matrix, residual)
                        if not frozen_matrix:
                            matrix, info = self._solve_matrix(matrix, latent, residual)
                    new = self.rows.scores(matrix, latent)
                total = residual + new
                scores[cid] = new
                snap = Snapshot(
                    step, cid, self.objective(total, fixed, random, latent, matrix),
                    self.held_out_rmse(fixed, random, latent, matrix),
                    fixed, dict(random), latent, matrix,
                )
                out.append(snap)
                if log is not None:
                    log(f"reference[{self.precision}] step {step} {cid}: "
                        f"objective {snap.objective:.3f} rmse {snap.rmse:.6f} {info}")
        return out

    def evaluate(self, model: Dict[str, np.ndarray]) -> Snapshot:
        """Objective and held-out RMSE of a given model (leaves ``fixed``,
        the random effects by name, ``latent``, ``matrix``) by the
        reference's own scorer."""
        with jax.default_matmul_precision(_mm(self.precision)):
            fixed = jnp.asarray(model["fixed"])
            random = {k: jnp.asarray(model[k]) for k in self.re}
            latent, matrix = jnp.asarray(model["latent"]), jnp.asarray(model["matrix"])
            snap = Snapshot(-1, "given", 0.0, None, fixed, random, latent, matrix)
            total = sum(self.train_scores(snap).values())
            snap.objective = self.objective(total, fixed, random, latent, matrix)
            snap.rmse = self.held_out_rmse(fixed, random, latent, matrix)
            return snap
