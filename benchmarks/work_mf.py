"""Algorithmic work of the factored coordinate's matrix solve: the
operations and bytes one objective evaluation over the implicit Kronecker
features needs whatever implements it (``work.py`` has the fixed effect's
and the random effects'; ``work.least_seconds`` turns either into a time).

Row (e, s) of a bucket's ``[E, S, D]`` block has the features
``kron(x[e, s], v[e])`` over ``vec(B)``, ``B`` the ``[d, k]`` projection
matrix of which entity ``e`` sees the ``D`` rows of its local columns. One
value-and-gradient evaluation is two maps, the margins ``x . (B v)`` and the
gradient's ``x' c v'``. Each map reads the float32 block once and the
gathered rows of ``B`` (``[E, D, k]``) once, and spends 2 FLOPs for every
element of the block and latent factor. Nothing of an implementation's
padding, gathers, scatters or contraction order is counted.
"""

from __future__ import annotations

from typing import Iterable, Tuple

MAPS_PER_EVALUATION = 2


def kron_map(entities: int, samples: int, dim: int, latent_factors: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one map over one bucket."""
    block = float(entities) * samples * dim
    gathered = float(entities) * dim * latent_factors
    return 2.0 * block * latent_factors, 4.0 * (block + gathered)


def kron_evaluation(buckets: Iterable[dict], latent_factors: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one value-and-gradient evaluation of the matrix
    solve over ``buckets`` (each ``{"entities", "samples", "dim"}``)."""
    flops = nbytes = 0.0
    for b in buckets:
        f, n = kron_map(b["entities"], b["samples"], b["dim"], latent_factors)
        flops += MAPS_PER_EVALUATION * f
        nbytes += MAPS_PER_EVALUATION * n
    return flops, nbytes
