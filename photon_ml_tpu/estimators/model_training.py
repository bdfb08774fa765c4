"""Single-GLM training entry: warm-started regularization sweep.

Reference parity: ModelTraining.trainGeneralizedLinearModel
(ModelTraining.scala:106-213): one optimization problem is reused across a
λ sweep sorted high→low, warm-starting each fit from the previous optimum
(:160-206). Optional per-coefficient variances from the inverse Hessian
diagonal (DistributedOptimizationProblem.scala:80-94).

TPU notes: the solver program is built once per (loss, optimizer settings)
and kept while later calls ask for the same one, specialized by ``jax.jit``
per argument shape: λ, the data, the start and a per-feature box are
arguments, so a sweep, a warm start, replaced offsets and a later
``train_glm`` call with equal optimizer settings all dispatch the program the
first call compiled. When ``data`` is sharded over a mesh's batch axis the
same code runs data-parallel with XLA-inserted psums — there is no separate
"distributed trainer".
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp

from photon_ml_tpu.losses.objective import GlmObjective, make_glm_objective
from photon_ml_tpu.losses.pointwise import loss_for_task
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration, OptimizerConfig
from photon_ml_tpu.opt.lbfgs import HISTORY_LAYOUT
from photon_ml_tpu.opt.solve import solve, solver_kind
from photon_ml_tpu.opt.state import SolveResult
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.parallel.mesh import mesh_attrs
from photon_ml_tpu.telemetry import note_jit_trace
from photon_ml_tpu.telemetry.span import get_tracer, span
from photon_ml_tpu.types import TaskType


@dataclasses.dataclass
class GlmFit:
    """One trained model of a sweep."""

    regularization_weight: float
    model: GeneralizedLinearModel
    result: SolveResult
    # per-iteration models (original feature space) when track_models was
    # requested — the reference's ModelTracker (ModelTracker.scala,
    # DistributedOptimizationProblem per-iteration tracking)
    tracked_models: Optional[List[GeneralizedLinearModel]] = None


def block_on_fit(fit: GlmFit) -> GlmFit:
    """Block until the fit's arrays are computed. ``train_glm`` returns
    unblocked pytrees (the async CD schedule relies on that to overlap the
    FE solve with RE work); timing and reconciliation code that needs the
    solve to have actually finished waits here."""
    import jax

    jax.block_until_ready(
        [leaf for leaf in jax.tree_util.tree_leaves(
            (fit.model, fit.result)
        ) if isinstance(leaf, jax.Array)]
    )
    return fit


# How many static keys' solve programs the process keeps. One: a kept program
# stays loaded on the device with the memory the runtime reserves for its
# temporaries (twice the L-BFGS history: 6.5 GB of a v5e's 16 in the
# benchmark's cells), so a second key's program may not find room beside the
# first's. The last key is what a CD run, a λ sweep and a loop over fits
# repeat; a caller that alternates keys rebuilds, as every call did before.
_MAX_SOLVE_PROGRAMS = 1


@functools.lru_cache(maxsize=_MAX_SOLVE_PROGRAMS)
def _solve_program(
    objective: GlmObjective, optimizer_config: OptimizerConfig, use_l1: bool
) -> Callable[..., SolveResult]:
    """The jitted ``(w0, data, l2, l1, box) -> SolveResult`` of one static
    key. ``jax.jit`` keys its caches on the function object, so the program
    has to be the same object at every ``train_glm`` call for the second call
    to dispatch what the first traced, lowered and loaded; everything that
    varies between calls is an argument, the regularization weights among
    them, so the key holds the optimizer's settings and nothing else of the
    configuration. ``box`` is ``None`` or a (lower, upper) pair of
    per-coefficient arrays. A plain jitted callable, never an ahead-of-time
    ``Compiled``: ``jax.clear_caches()`` still frees the program, and the next
    call builds it again."""
    configuration = GlmOptimizationConfiguration(optimizer_config=optimizer_config)
    kind = solver_kind(configuration, 1.0 if use_l1 else 0.0)

    def glm_solve(w0, data, l2, l1, box):
        note_jit_trace("glm_solve", kind)  # fires only on a (re)trace
        return solve(
            objective,
            w0,
            data,
            configuration,
            l2_weight=l2,
            l1_weight=l1 if use_l1 else 0.0,
            box=box,
        )

    return jax.jit(glm_solve)


def train_glm(
    data: LabeledData,
    task: TaskType,
    configuration: GlmOptimizationConfiguration,
    regularization_weights: Optional[Sequence[float]] = None,
    initial_model: Optional[GeneralizedLinearModel] = None,
    warm_start: bool = True,
    compute_variances: bool = False,
    track_models: bool = False,
    intercept_index: Optional[int] = None,
    box_constraints=None,
) -> List[GlmFit]:
    """Train one GLM per regularization weight, warm-starting down the sorted
    sweep. Returns fits in the caller's requested order.

    Coefficients are returned in the ORIGINAL feature space: when ``data.norm``
    is set, training runs in normalized space and the optimum is mapped back
    (reference NormalizationContext.transformModelCoefficients / Driver flow).
    """
    with span(
        "glm/train",
        optimizer=configuration.optimizer_config.optimizer.name,
        weights=1 if regularization_weights is None else len(regularization_weights),
    ):
        if regularization_weights is None:
            regularization_weights = [configuration.regularization_weight]
        if track_models:
            configuration = dataclasses.replace(
                configuration,
                optimizer_config=dataclasses.replace(
                    configuration.optimizer_config, track_coefficients=True
                ),
            )

        dim = data.dim
        if initial_model is not None:
            # initial_model carries ORIGINAL-space coefficients; map into the
            # normalized training space before warm-starting.
            w = initial_model.coefficients.means
            if data.norm is not None:
                w = data.norm.inverse_transform_model_coefficients(w, intercept_index)
        elif hasattr(data.features, "zero_coefficients"):
            w = data.features.zero_coefficients()  # laid out over a mesh
        else:
            w = jnp.zeros((dim,), dtype=jnp.float32)

        reg = configuration.regularization
        use_l1 = any(reg.l1_weight(lw) > 0 for lw in regularization_weights)

        # An explicit 0.0 l1_weight pins the solver to LBFGS/TRON even when the
        # configuration's own regularization_weight would imply L1 (the sweep
        # weights are authoritative).
        # box_constraints arrive in the ORIGINAL feature space (the reference's
        # per-feature constraint map, GLMSuite); training may run in normalized
        # space, where w_orig = factor .* w_norm (componentwise, factor > 0), so
        # the bounds map by the same positive diagonal. Shift normalization
        # mixes the intercept non-componentwise — an explicitly-bounded
        # intercept cannot be honored there and is rejected.
        if box_constraints is not None and data.norm is not None:
            lo, hi = box_constraints
            if data.norm.shift is not None and intercept_index is not None:
                import numpy as np

                if (np.isfinite(np.asarray(lo)[intercept_index])
                        or np.isfinite(np.asarray(hi)[intercept_index])):
                    raise ValueError(
                        "an intercept box constraint cannot be combined with "
                        "shift normalization (the intercept mixes all "
                        "coefficients there); constrain only non-intercept "
                        "features or use a factor-only normalization"
                    )
            factor = data.norm.factor
            if factor is not None:
                lo = jnp.asarray(lo) / factor
                hi = jnp.asarray(hi) / factor
            box_constraints = (lo, hi)
        if box_constraints is not None:
            # uploaded once for the whole sweep, not at every solve
            box_constraints = tuple(
                None if b is None else jnp.asarray(b)
                for b in box_constraints
            )
        objective = make_glm_objective(loss_for_task(task))
        solver = _solve_program(objective, configuration.optimizer_config, use_l1)
        # what a traced run's glm/solve spans say of the curvature history
        kind = solver_kind(configuration, 1.0 if use_l1 else 0.0)
        layout = "none" if kind == "tron" else HISTORY_LAYOUT
        on_mesh = mesh_attrs(getattr(data.features, "mesh", None))
        # the objective's functions are the same objects at every call, so
        # this wrapper finds the program an earlier call's wrapper compiled
        hess_diag = jax.jit(objective.hessian_diag) if compute_variances else None

        # high -> low so each warm start begins from a smoother problem
        # (reference ModelTraining.scala:160-206)
        sweep = sorted(regularization_weights, reverse=True)
        fits: dict[float, GlmFit] = {}
        for lam in sweep:
            l2 = jnp.float32(reg.l2_weight(lam))
            l1 = jnp.float32(reg.l1_weight(lam))
            with span(
                "glm/solve", regularization_weight=float(lam), **on_mesh
            ) as solving:
                result = solver(w, data, l2, l1, box_constraints)
                if get_tracer().enabled:
                    # a traced run waits for the solve here, so that the span
                    # holds the device's work and can say what it counted
                    jax.block_until_ready(result)
                    solving.set_attrs(
                        iterations=int(result.iterations),
                        evaluations=int(result.evaluations),
                        hessian_vecs=int(result.hessian_vecs),
                        rejected_steps=int(result.rejected_steps),
                        history_layout=layout,
                    )
            if warm_start:
                w = result.w

            variances = None
            if compute_variances:
                # var_j ~= 1 / (H_jj + eps) (reference
                # DistributedOptimizationProblem.scala:80-94)
                diag = hess_diag(result.w, data, l2)
                variances = 1.0 / (diag + 1e-12)

            w_out = result.w
            if data.norm is not None:
                w_out = data.norm.transform_model_coefficients(w_out, intercept_index)
                if variances is not None:
                    variances = data.norm.transform_model_variances(variances, intercept_index)
            model = GeneralizedLinearModel(
                coefficients=Coefficients(means=w_out, variances=variances), task=task
            )

            tracked = None
            if track_models and result.w_history is not None:
                tracked = []
                iters = int(result.iterations)
                for w_i in result.w_history[: iters + 1]:
                    if data.norm is not None:
                        w_i = data.norm.transform_model_coefficients(
                            w_i, intercept_index
                        )
                    tracked.append(
                        GeneralizedLinearModel(
                            coefficients=Coefficients(means=w_i), task=task
                        )
                    )
            fits[lam] = GlmFit(
                regularization_weight=lam, model=model, result=result,
                tracked_models=tracked,
            )

        return [fits[lam] for lam in regularization_weights]
