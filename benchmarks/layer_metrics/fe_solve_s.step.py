"""Host seconds in the fixed-effect solve: the ``fe/solve`` spans of the
window where a coordinate makes them, the driver's wall time around the
solve call where it calls ``train_glm`` itself; over the steps."""
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "fe_solve_s.step", "s/step", "program_span"


def read(context):
    solves = _spans.in_window(context, "fe/solve")
    if solves:
        return _spans.seconds(solves) / context["steps"]
    total = context["times"].get("solve_s_in_window")
    return None if total is None else total / context["steps"]
