"""Faults planted in the timed path, for the tests and for the readings on
the chip: each has to make ``correct`` come out false.

- ``unchanged``: a step returns its state unchanged. For ``cd_fit`` every
  coordinate update that is handed a model (those of a fit's second outer
  iteration) hands it back; for ``refit`` the fit hands back the zero model
  it started from.
- ``half_batch``: the program's entry leaves out the second half of the
  training rows and fits the rest; the reference keeps all of them. For
  ``cd_fit``, ``GameEstimator.fit_multiple`` slices its data to the first
  half; for ``refit``, ``train_glm`` gives the second half the weight 0.
- ``carried_over`` (``cd_fit`` alone): a fit starts from the model its
  predecessor returned and not from nothing: ``fit_multiple`` runs with
  ``warm_start=True`` whatever it was asked.

One chip, one program, no token: the exchange between chips and an altered
answer are not faults these cells can have.
"""

from __future__ import annotations

import contextlib
import dataclasses
from unittest import mock

import numpy as np

FAULTS = ("unchanged", "half_batch")
CARRIED_OVER = "carried_over"


@contextlib.contextmanager
def planted(fault: str, driver_module):
    cd_fit = driver_module.__name__.endswith("cd_fit")
    if fault not in FAULTS and not (cd_fit and fault == CARRIED_OVER):
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}, and {CARRIED_OVER} for cd_fit")
    if fault == CARRIED_OVER:
        from photon_ml_tpu.estimators.game import GameEstimator

        real_fit = GameEstimator.fit_multiple

        def warm_started_fit(self, *args, **kwargs):
            return real_fit(self, *args, **{**kwargs, "warm_start": True})

        with mock.patch.object(GameEstimator, "fit_multiple", warm_started_fit):
            yield
    elif fault == "half_batch" and cd_fit:
        from photon_ml_tpu.estimators.game import GameEstimator

        real_fit = GameEstimator.fit_multiple

        def first_half_fit(self, data, *args, **kwargs):
            mask = np.arange(data.num_rows) < data.num_rows // 2
            return real_fit(self, data.slice_rows(mask), *args, **kwargs)

        with mock.patch.object(GameEstimator, "fit_multiple", first_half_fit):
            yield
    elif fault == "half_batch":
        from photon_ml_tpu.estimators import model_training as M

        real_train = M.train_glm

        def first_half_train(data, *args, **kwargs):
            n = data.weights.shape[-1]
            weights = data.weights.at[n // 2:].set(0.0)
            return real_train(dataclasses.replace(data, weights=weights), *args, **kwargs)

        with mock.patch.object(M, "train_glm", first_half_train):
            yield
    elif cd_fit:
        from photon_ml_tpu.algorithm import coordinate as C

        def unchanged(real):
            def update(self, model, residual):
                return real(self, model, residual) if model is None else model
            return update

        with contextlib.ExitStack() as stack:
            for cls in (C.FixedEffectCoordinate, C.RandomEffectCoordinate):
                for name in ("update_model", "update_model_device"):
                    stack.enter_context(
                        mock.patch.object(cls, name, unchanged(getattr(cls, name)))
                    )
            yield
    else:
        import jax.numpy as jnp

        from photon_ml_tpu.estimators import model_training as M

        real = M.train_glm

        def zero_model(*args, **kwargs):
            fits = real(*args, **kwargs)
            return [
                dataclasses.replace(f, model=f.model.replace(
                    coefficients=f.model.coefficients.replace(
                        means=jnp.zeros_like(f.model.coefficients.means))))
                for f in fits
            ]

        with mock.patch.object(M, "train_glm", zero_model):
            yield
