"""Host seconds of set-up in the routed plans: the union of the
``route/plan`` spans before the window, each the key's hash and the plan
file's read and decode where the plan cache holds it, else the routing and
the file's write."""
from benchmarks.layer_metrics import _setup

NAME, UNIT, SOURCE = "setup_plan_s", "s", "program_span"


def read(context):
    return _setup.union_before(context, ("route/plan",))
