"""The arithmetic that decides ``correct``: gaps between what the program
produced and what the plain reference produced, each held to a limit of its
own (the limits live in the workload's traffic file, set from readings on
the chip; PERF.md gives the readings).

Leaves are compared by the gap between the norms, not the norm of the
difference, measured against the reference's norm of that leaf or of the
median leaf, whichever is larger.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np


def relative_gap(program: float, reference: float) -> float:
    return abs(program - reference) / max(abs(reference), 1e-30)


def worst_leaf_norm_gap(program: Dict[str, np.ndarray], reference: Dict[str, np.ndarray],
                        log: Optional[Callable[[str], None]] = None) -> float:
    """Largest over the leaves of | ||p|| - ||r|| | / max(||r||, median ||r||).
    ``log`` is told every leaf's norms and its gap against its own norm too,
    so that a run shows what the median does to the smallest leaf."""
    names = sorted(reference)
    ref_norms = {k: float(np.linalg.norm(np.asarray(reference[k], np.float64))) for k in names}
    median = float(np.median(list(ref_norms.values())))
    worst = 0.0
    for k in names:
        p = float(np.linalg.norm(np.asarray(program[k], np.float64)))
        gap = abs(p - ref_norms[k])
        worst = max(worst, gap / max(ref_norms[k], median, 1e-30))
        if log is not None:
            log(f"leaf {k}: reference norm {ref_norms[k]:.6g}, program norm {p:.6g}, gap over its "
                f"own norm {gap / max(ref_norms[k], 1e-30):.3g}, over max(own, median "
                f"{median:.6g}) {gap / max(ref_norms[k], median, 1e-30):.3g}")
    return worst


def picked_update(validation: Sequence[float], complete_from: int) -> int:
    """Which update's model a fit returns, by the rule the configuration
    states: the held-out metric's first maximum over the updates at which
    every coordinate has a model. In a fit from nothing those start at
    update ``complete_from`` (the number of coordinates less one)."""
    if not validation:
        return -1
    best = min(complete_from, len(validation) - 1)
    for i in range(best + 1, len(validation)):
        if validation[i] > validation[best]:
            best = i
    return best


def training_numbers(histories: List[dict], models: Dict[int, dict], reference: list,
                     complete_from: int,
                     log: Optional[Callable[[str], None]] = None) -> Dict[str, float]:
    """Every compared fit (the keys of ``models``: the same work each, a
    whole fit from the zero model) against the reference's run, a snapshot a
    block update. loss_gap: worst relative gap of the objective after every
    update. auc_gap: worst absolute gap of the held-out AUC there.
    change_gap: worst leaf's gap of norms of the model the fit returned (the
    start is the zero model, so a model's norm is the norm of its change)."""
    loss_gap = auc_gap = change_gap = 0.0
    for fit, model in models.items():
        hist = histories[fit]
        if len(hist["objective"]) != len(reference) or len(hist["validation"]) != len(reference):
            return {"loss_gap": math.inf, "auc_gap": math.inf, "change_gap": math.inf}
        losses = [relative_gap(p, snap.objective) for p, snap in zip(hist["objective"], reference)]
        aucs = [abs(p - snap.auc) for p, snap in zip(hist["validation"], reference)]
        loss_gap, auc_gap = max(loss_gap, *losses), max(auc_gap, *aucs)
        if log is not None:
            log(f"fit {fit} by update: objective gap " + " ".join(f"{g:.3g}" for g in losses)
                + " ; auc gap " + " ".join(f"{g:.3g}" for g in aucs))
        snap = reference[picked_update(hist["validation"], complete_from)]
        leaves = {"fixed": np.asarray(snap.fixed)}
        leaves.update({k: np.asarray(v) for k, v in snap.random.items()})
        change_gap = max(change_gap, worst_leaf_norm_gap(
            model, leaves, log and (lambda line, fit=fit: log(f"fit {fit} {line}"))))
    return {"loss_gap": _finite(loss_gap), "auc_gap": _finite(auc_gap),
            "change_gap": _finite(change_gap)}


def scored_gaps(histories: List[dict], models: Dict[int, dict], complete_from: int,
                evaluate: Callable) -> Dict[str, float]:
    """Every kept model that a fit returned, scored by the reference: the
    relative gap between the objective the program reported for the update
    it returned and the reference scorer's objective of that same model
    (scored_objective_gap), and the absolute gap between the held-out AUC it
    reported there and the reference scorer's (scored_auc_gap). They read the
    arithmetic of the timed path's maps, score plane, loss and evaluator, free
    of where the solvers stopped; the worst over the kept fits. Two numbers,
    because a lower precision's error in either is a sum of roundings that may
    cancel on a seed: a sum over the training rows in the one, a count of
    swapped held-out pairs in the other."""
    objective = auc = 0.0
    for fit, model in models.items():
        hist = histories[fit]
        i = picked_update(hist["validation"], complete_from)
        scored = evaluate(model)
        objective = max(objective, relative_gap(hist["objective"][i], scored.objective))
        auc = max(auc, abs(hist["validation"][i] - scored.auc))
    return {"scored_objective_gap": _finite(objective), "scored_auc_gap": _finite(auc)}


def repeat_gap(histories: List[dict]) -> Dict[str, float]:
    """Every fit of the window against the warm-up fit (``histories[0]``):
    the worst relative gap of the objective after the same block update.
    Each fit starts from the zero model on the same data through the same
    kept programs, so each has to report what the first did; a fit that
    starts from its predecessor's model, or a kept program or donated buffer
    that carries state from fit to fit, shows here."""
    first, worst = histories[0]["objective"], 0.0
    for hist in histories[1:]:
        if len(hist["objective"]) != len(first):
            return {"repeat_gap": math.inf}
        for p, q in zip(hist["objective"], first):
            worst = max(worst, relative_gap(p, q))
    return {"repeat_gap": _finite(worst)}


def _finite(x: float) -> float:
    return float(x) if math.isfinite(x) else math.inf


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, [(name, number, limit)]) — every limit must be met by a
    number that was read; a limit with no number, or a NaN, is a failure."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name, math.nan)
        rows.append((name, value, limit))
        if not (isinstance(value, float) and value <= limit):
            ok = False
    return ok, rows
