"""Value-and-gradient evaluations of the fixed-effect solves in the window,
counted by the solver where they happen (``SolveResult.evaluations``: line
search and projection retries included) and carried by the ``glm/solve``
spans, over the steps. Each is two sparse maps."""
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "fe_evals.step", "count/step", "program_counter"


def read(context):
    counts = [s["attrs"]["evaluations"] for s in _spans.in_window(context, "glm/solve")
              if s["attrs"].get("evaluations") is not None]
    return sum(counts) / context["steps"] if counts else None
