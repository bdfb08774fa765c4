"""Trace, lowering and backend-compile seconds that JAX reported inside the
measured window (jax.monitoring), over the steps."""
NAME, UNIT, SOURCE = "compile_s.step", "s/step", "program_span"


def read(context):
    lo, hi = context["window"]
    return context["compile"].window(lo, hi)[0] / context["steps"]
