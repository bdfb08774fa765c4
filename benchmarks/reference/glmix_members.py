"""Plain reference for a GLMix model whose random effects hold a
deployment's share of entities: ``glmix.py``'s block coordinate descent (the
fixed effect by L-BFGS to its optimum, every entity by damped Newton, the
objective and the held-out AUC after every block update), with the entities
grouped so that a million of them fit a chip.

``glmix.py`` pads every entity to the largest one: ``[n_entities, width,
dim]``, 64 GB for 2^20 members under a 1,024-row tail. Here the entities are
sorted by their row count, rounded up to a power of two, into width classes,
and each class is solved in chunks of ``CHUNK`` entities or, in a narrow
class, as many as fill ``CHUNK_ROWS`` rows (a class with fewer in one chunk
of the next power of two), each chunk padded to its class's width and its
last one with entities that hold no row. The entity is every array's minor
axis, so a product or a sum over a member's rows or features is an
elementwise float32 operation over whole lanes. Nothing of the program is
used: no bucket, no bucket planner, no layout. Where the configuration caps
an entity's active rows, the kept rows are ``glmix.py``'s: the ``cap`` of
smallest key among keys drawn by
``numpy.random.default_rng(sample_seed).random(n)``.

It runs in float32 (or, as the control, with every product's operands in
bfloat16), after the window has closed and the program's state is freed.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import solvers
from benchmarks.reference.glmix import GlmixReference

# entities a chunk, at the least: one chunk of the widest class (1,024
# rows of 16 features) is 256 MB of float32
CHUNK = 4096
# rows a chunk of a narrow class holds, at the most: the narrow classes take
# more entities a chunk, so that a solve is a few dozen chunks, not hundreds
CHUNK_ROWS = 1 << 18


class _WidthClasses:
    """One random effect's kept training rows, grouped for the solves: the
    entities in order of their width class (``order``), and a chunk a run of
    ``size`` consecutive ones of one class: (how many of its lanes are
    entities, the rows' positions [width, size], their mask, the rows'
    features [width, dim, size], gathered once on the device from ``x``, and
    their labels [width, size]), the entity the minor axis; a chunk's last
    lanes may hold no entity and no row. ``back`` is each entity's lane in
    the chunks' results laid end to end."""

    def __init__(self, ids: np.ndarray, x: jax.Array, labels: np.ndarray, n_entities: int,
                 cap: Optional[int], sample_seed: int):
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
        rows, ent = order[keep], sorted_ids[keep]
        counts = np.bincount(ent, minlength=n_entities)
        first = np.searchsorted(ent, np.arange(n_entities))
        widths = 1 << np.ceil(np.log2(np.maximum(counts, 1))).astype(np.int64)
        self.order = np.argsort(widths, kind="stable")
        self.back = np.zeros(n_entities, np.int64)
        self.chunks: List[tuple] = []
        lanes = 0
        for width in np.unique(widths):
            members = np.flatnonzero(widths == width)
            # a class smaller than a chunk is one chunk of the next power of two
            size = min(max(CHUNK, CHUNK_ROWS // int(width)),
                       1 << (int(members.size) - 1).bit_length())
            for lo in range(0, members.size, size):
                e = members[lo:lo + size]
                c = counts[e]
                lane = np.repeat(np.arange(e.size), c)
                slot = np.arange(lane.size) - np.repeat(np.cumsum(c) - c, c)
                pos = np.zeros((width, size), np.int32)
                mask = np.zeros((width, size), np.float32)
                pos[slot, lane] = rows[first[e][lane] + slot]
                mask[slot, lane] = 1.0
                self.back[e] = lanes + np.arange(e.size)
                lanes += size
                pos_d, mask_d = jnp.asarray(pos), jnp.asarray(mask)
                self.chunks.append((
                    int(e.size), pos_d, mask_d,
                    (x[pos_d] * mask_d[..., None]).transpose(0, 2, 1),
                    jnp.asarray(labels[pos] * mask),
                ))


def _spd_solve(a, b):
    """``a^-1 b`` for a batch of symmetric positive definite ``a`` [D, D, E]
    and ``b`` [D, E], the batch minor: Cholesky's column algorithm, then the
    two triangular substitutions, a row or column at a time, every step an
    elementwise float32 operation on whole lanes."""
    d = a.shape[0]
    rows = jnp.arange(d)[:, None]
    take = functools.partial(jax.lax.dynamic_index_in_dim, keepdims=False)
    put = jax.lax.dynamic_update_index_in_dim

    def column(j, low):  # low [i, k, E]: the factor's columns k < j
        v = take(a, j, 1) - (low * take(low, j, 0)[None]).sum(1)
        return put(low, jnp.where(rows >= j, v / jnp.sqrt(take(v, j, 0)), 0.0), j, 1)

    low = jax.lax.fori_loop(0, d, column, jnp.zeros_like(a))
    diag = jnp.diagonal(low, axis1=0, axis2=1).T

    def forward(i, y):  # L y = b
        return put(y, (take(b, i, 0) - (take(low, i, 0) * y).sum(0)) / take(diag, i, 0), i, 0)

    def backward(n, x):  # L^T x = y
        i = d - 1 - n
        return put(x, (take(y, i, 0) - (take(low, i, 1) * x).sum(0)) / take(diag, i, 0), i, 0)

    y = jax.lax.fori_loop(0, d, forward, jnp.zeros_like(b))
    return jax.lax.fori_loop(0, d, backward, jnp.zeros_like(b))


@functools.partial(jax.jit, static_argnums=(6, 7))
def _solve_chunk(x, labels, offsets, pos, mask, theta0, l2, precision):
    """One chunk's entities, their L2-regularised logistic fits at once by
    damped Newton, as ``solvers.solve_entities_newton`` does them (the same
    30 steps, each halved up to 8 times until the objective does not rise;
    the halving stops once every lane's step holds). The arrays hold the
    entity on their minor axis (``x`` [S, D, E]; ``labels``, ``pos``,
    ``mask`` [S, E]), so every product and sum is an elementwise float32
    operation over whole lanes: a product of two operands, each rounded to
    ``precision`` first, as a matmul of them with float32 sums is; the
    Newton system is solved by its Cholesky factor (``_spd_solve``; the
    Hessian is positive definite, ``l2`` > 0). The residual's offsets are
    the one thing gathered here. ``theta0`` and the result are [E, D]."""
    offsets = offsets[pos] * mask
    f32 = jnp.float32

    def op(v):
        return solvers.operand(v, precision).astype(f32)

    xo = op(x)

    def margins(theta):  # theta [D, E] -> [S, E]
        return (xo * op(theta)[None]).sum(1) + offsets

    def value(theta):
        loss = mask * solvers.logistic_value(margins(theta), labels)
        return loss.sum(0) + 0.5 * l2 * (theta * theta).sum(0)

    def step(_, theta):
        s = jax.nn.sigmoid(margins(theta))
        d1, d2 = mask * (s - labels), mask * s * (1.0 - s)
        grad = (xo * op(d1)[:, None]).sum(0) + l2 * theta
        xw = op(x * d2[:, None])
        hess = (xw[:, :, None] * xo[:, None]).sum(0)
        hess = hess + l2 * jnp.eye(theta.shape[0], dtype=f32)[..., None]
        delta = _spd_solve(hess, grad)
        f0 = value(theta)

        def lower(t):
            return value(theta - t[None] * delta) <= f0

        def unsure(carry):
            k, _, ok = carry
            return (k < 8) & ~jnp.all(ok)

        def halve(carry):
            k, t, ok = carry
            t = jnp.where(ok, t, 0.5 * t)
            return k + 1, t, lower(t)

        # halve a lane's step until the objective does not rise, at most 8
        # times (the loop ends once every lane's does not)
        t = jnp.ones_like(f0)
        _, t, _ = jax.lax.while_loop(unsure, halve, (0, t, lower(t)))
        cand = theta - t[None] * delta
        return jnp.where((value(cand) <= f0)[None], cand, theta)

    return jax.lax.fori_loop(0, 30, step, theta0.T).T


class GlmixMembersReference(GlmixReference):
    """``GlmixReference`` with every random effect in width classes."""

    def __init__(self, config: dict, problem, precision: str = "float32"):
        super().__init__(dict(config, random_effects={}), problem, precision)
        self.config = config
        self.re = config.get("random_effects", {})
        train = problem.train
        self.blocks = {
            name: _WidthClasses(
                train.entities[name], self.x[name], train.labels, re["n_entities"],
                re.get("active_cap"), int(re.get("sample_seed", 0)),
            )
            for name, re in self.re.items()
        }

    def _solve_entities(self, name, theta0, offsets):
        classes = self.blocks[name]
        l2 = float(self.re[name]["regularization_weight"])
        # the entities in class order, each chunk's start a slice of it (a
        # last chunk's lanes past its entities start from whatever follows)
        start = jnp.pad(theta0[classes.order], ((0, CHUNK), (0, 0)))
        solved, at = [], 0
        for real, pos, mask, x, labels in classes.chunks:
            theta = jax.lax.dynamic_slice_in_dim(start, at, pos.shape[1])
            solved.append(_solve_chunk(x, labels, offsets, pos, mask, theta, l2,
                                       self.precision))
            at += real
        return jnp.concatenate(solved)[classes.back]
