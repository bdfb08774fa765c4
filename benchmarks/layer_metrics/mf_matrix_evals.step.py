"""Value-and-gradient evaluations of the factored coordinate's matrix solves
in the window, counted by the solver where they happen
(``SolveResult.evaluations``) and carried by the ``mf/solve_matrix`` spans,
over the steps. Each is two maps over the implicit Kronecker features."""
from benchmarks.layer_metrics import _mf

NAME, UNIT, SOURCE = "mf_matrix_evals.step", "count/step", "program_counter"


def read(context):
    counts = [int(s["attrs"]["evaluations"]) for s in _mf.matrix_solves(context)]
    return sum(counts) / context["steps"] if counts else None
