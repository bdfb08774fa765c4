"""Plain reference for GLMix training: block coordinate descent over a
sparse fixed effect and per-entity random effects, each block solved to its
optimum (solvers.py), with the training objective and the held-out AUC read
after every block update, as the program's driver reads them.

What it takes: the seeded rows (datagen.Rows) and the numbers of the
configuration file. Nothing of the program: no routed plan, no bucket, no
model. It runs after the window has closed and the program's state is freed.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import solvers


@dataclasses.dataclass
class Snapshot:
    """The model after one block update, with what was read there."""

    step: int
    coordinate: str
    objective: float
    auc: Optional[float]
    fixed: jax.Array                  # [n_cols]
    random: Dict[str, jax.Array]      # name -> [n_entities, dim]


class _EntityBlocks:
    """One random effect's training rows, grouped by entity and padded to
    the largest group. Where the configuration caps an entity's active
    rows, the kept rows are the ``cap`` of smallest key among keys drawn by
    ``numpy.random.default_rng(sample_seed).random(n)``: the uniform sample
    without replacement that the configuration states, written out."""

    def __init__(self, ids: np.ndarray, x: np.ndarray, labels: np.ndarray,
                 n_entities: int, cap: Optional[int], sample_seed: int):
        n = ids.shape[0]
        if cap is not None:
            keys = np.random.default_rng(sample_seed).random(n)
            order = np.lexsort((keys, ids))
        else:
            order = np.argsort(ids, kind="stable")
        sorted_ids = ids[order]
        starts = np.searchsorted(sorted_ids, np.arange(n_entities))
        rank = np.arange(n) - starts[sorted_ids]
        keep = rank < cap if cap is not None else np.ones(n, bool)
        rows, ent, slot = order[keep], sorted_ids[keep], rank[keep]
        width = int(slot.max()) + 1 if rows.size else 1
        pos = np.zeros((n_entities, width), np.int32)
        mask = np.zeros((n_entities, width), np.float32)
        pos[ent, slot] = rows
        mask[ent, slot] = 1.0
        self.pos = jnp.asarray(pos)
        self.mask = jnp.asarray(mask)
        self.x = jnp.asarray(x)[self.pos] * self.mask[..., None]
        self.labels = jnp.asarray(labels)[self.pos] * self.mask


class GlmixReference:
    def __init__(self, config: dict, problem, precision: str = "float32"):
        if config["task"] != "LOGISTIC_REGRESSION":
            raise ValueError("the GLMix reference is written for the logistic task")
        self.config = config
        self.precision = precision
        self.order: List[str] = list(config["update_order"])
        train, held = problem.train, problem.held_out
        self.n_cols = problem.n_cols
        self.features = solvers.SparseRows(
            train.cols, train.vals, problem.n_cols, precision
        )
        self.labels = jnp.asarray(train.labels)
        self.fe_l2 = float(config["fixed_effect"]["regularization_weight"])
        self.fe_gradient_scale = 0.0
        self.re = config.get("random_effects", {})
        self.ids = {k: jnp.asarray(v, dtype=jnp.int32) for k, v in train.entities.items()}
        self.x = {k: jnp.asarray(v) for k, v in train.entity_x.items()}
        self.blocks = {
            name: _EntityBlocks(
                train.entities[name], train.entity_x[name], train.labels,
                re["n_entities"], re.get("active_cap"), int(re.get("sample_seed", 0)),
            )
            for name, re in self.re.items()
        }
        self.held = held
        if held is not None:
            self.held_features = solvers.SparseRows(
                held.cols, held.vals, problem.n_cols, precision
            )
            self.held_ids = {k: jnp.asarray(v, dtype=jnp.int32) for k, v in held.entities.items()}
            self.held_x = {k: jnp.asarray(v) for k, v in held.entity_x.items()}

    # -- scores ---------------------------------------------------------
    def _entity_scores(self, x, ids, theta):
        p = self.precision
        prod = solvers.operand(x, p) * solvers.operand(theta, p)[ids]
        return prod.astype(jnp.float32).sum(-1)

    def train_scores(self, fixed, random) -> Dict[str, jax.Array]:
        scores = {"fixed": self.features.matvec(fixed)}
        for name, theta in random.items():
            scores[name] = self._entity_scores(self.x[name], self.ids[name], theta)
        return scores

    def objective(self, total, fixed, random) -> float:
        loss = float(jnp.sum(solvers.logistic_value(total, self.labels)))
        reg = 0.5 * self.fe_l2 * float(jnp.vdot(fixed, fixed)) if fixed is not None else 0.0
        for name, theta in random.items():
            reg += 0.5 * float(self.re[name]["regularization_weight"]) * float(jnp.vdot(theta, theta))
        return loss + reg

    def held_out_auc(self, fixed, random) -> Optional[float]:
        if self.held is None:
            return None
        z = jnp.zeros((self.held.n,), jnp.float32)
        if fixed is not None:
            z = z + self.held_features.matvec(fixed)
        for name, theta in random.items():
            z = z + self._entity_scores(self.held_x[name], self.held_ids[name], theta)
        return solvers.auc(np.asarray(z), self.held.labels)

    # -- block solves ---------------------------------------------------
    def _solve_fixed(self, w0, offsets):
        l2 = jnp.float32(self.fe_l2)
        labels, feats = self.labels, self.features

        @jax.jit
        def pointwise(z, w):
            zz = z + offsets
            value = jnp.sum(solvers.logistic_value(zz, labels)) + 0.5 * l2 * jnp.vdot(w, w)
            return value, solvers.logistic_d1(zz, labels)

        def value_and_grad(w):
            value, d1 = pointwise(feats.matvec(w), w)
            return value, feats.rmatvec(d1) + l2 * w

        w, info = solvers.minimize_lbfgs(
            value_and_grad, w0, gradient_scale=self.fe_gradient_scale
        )
        # the gradient at the zero model scales every later, warm-started solve
        self.fe_gradient_scale = max(self.fe_gradient_scale, info["gradient_norm_start"])
        return w, info

    def _solve_entities(self, name, theta0, offsets):
        b = self.blocks[name]
        return solvers.solve_entities_newton(
            b.x, b.labels, offsets[b.pos] * b.mask, b.mask, theta0,
            float(self.re[name]["regularization_weight"]), self.precision,
        )

    # -- the descent ----------------------------------------------------
    def run(self, steps: int, log=None, start: Optional[Snapshot] = None) -> List[Snapshot]:
        """``steps`` outer iterations from the zero model (or from the model
        of ``start``: a fit that is handed its predecessor's); a snapshot
        after every block update."""
        fixed: Optional[jax.Array] = None
        random: Dict[str, jax.Array] = {}
        scores: Dict[str, jax.Array] = {}
        total = jnp.zeros_like(self.labels)
        if start is not None:
            fixed, random = start.fixed, dict(start.random)
            scores = self.train_scores(fixed, random)
            total = sum(scores.values())
        out: List[Snapshot] = []
        for step in range(steps):
            for cid in self.order:
                own = scores.get(cid)
                residual = total - own if own is not None else total
                if cid == "fixed":
                    w0 = fixed if fixed is not None else jnp.zeros((self.n_cols,), jnp.float32)
                    fixed, info = self._solve_fixed(w0, residual)
                    new = self.features.matvec(fixed)
                else:
                    re = self.re[cid]
                    theta0 = random.get(cid)
                    if theta0 is None:
                        theta0 = jnp.zeros((re["n_entities"], re["dim"]), jnp.float32)
                    random = dict(random)
                    random[cid] = self._solve_entities(cid, theta0, residual)
                    new = self._entity_scores(self.x[cid], self.ids[cid], random[cid])
                    info = {}
                total = residual + new
                scores[cid] = new
                snap = Snapshot(
                    step, cid, self.objective(total, fixed, random),
                    self.held_out_auc(fixed, random), fixed, dict(random),
                )
                out.append(snap)
                if log is not None:
                    log(f"reference[{self.precision}] step {step} {cid}: "
                        f"objective {snap.objective:.3f} auc {snap.auc} {info}")
        return out

    def evaluate(self, fixed, random) -> Snapshot:
        """Objective and held-out AUC of a given model by the reference's
        own scorer (used on the model the window's last step returned)."""
        fixed = jnp.asarray(fixed)
        random = {k: jnp.asarray(v) for k, v in random.items()}
        total = sum(self.train_scores(fixed, random).values())
        return Snapshot(-1, "given", self.objective(total, fixed, random),
                        self.held_out_auc(fixed, random), fixed, random)
