"""Self time of the CD driver: every ``cd/outer_iter`` span of the window
less its ``cd/coordinate`` children (objective, validation and bookkeeping
stay in), host seconds over the steps."""
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "cd_outside_s.step", "s/step", "program_span"


def read(context):
    outer = _spans.in_window(context, "cd/outer_iter")
    if not outer:
        return None
    inner = _spans.in_window(context, "cd/coordinate")
    return (_spans.seconds(outer) - _spans.seconds(inner)) / context["steps"]
