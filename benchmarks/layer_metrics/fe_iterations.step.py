"""Fixed-effect solver iterations in the window, counted by the solver
(``SolveResult.iterations``) and carried by the ``glm/solve`` spans, summed
over every solve of the window (a GLMix fit of two outer iterations makes
two), over the steps. An iteration is at least one value-and-gradient;
``fe_evals.step`` counts those."""
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "fe_iterations.step", "count/step", "program_counter"


def read(context):
    counts = [a["iterations"] for a in _spans.window_solves(context)
              if a.get("iterations") is not None]
    return sum(counts) / context["steps"] if counts else None
