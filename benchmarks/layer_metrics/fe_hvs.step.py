"""Hessian-vector products of the fixed-effect solves in the window, counted
by TRON where they happen (``SolveResult.hessian_vecs``: the CG steps of
every trust-region step, summed in the loop carry) and carried by the
``glm/solve`` spans, over the steps. Each needs two sparse maps. None where
the program's spans carry no such count (any commit before PR 28)."""
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "fe_hvs.step", "count/step", "program_counter"


def read(context):
    counts = [s["attrs"]["hessian_vecs"] for s in _spans.in_window(context, "glm/solve")
              if s["attrs"].get("hessian_vecs") is not None]
    return sum(counts) / context["steps"] if counts else None
