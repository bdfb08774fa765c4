"""Value-and-gradient evaluations of the fixed-effect solves in the window,
counted by the solver where they happen (``SolveResult.evaluations``: line
search and projection retries included) and carried by the ``glm/solve``
spans, over the steps. Each is two sparse maps."""
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "fe_evals.step", "count/step", "program_counter"


def read(context):
    counts = [a["evaluations"] for a in _spans.window_solves(context)]
    return sum(counts) / context["steps"] if counts else None
