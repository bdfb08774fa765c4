"""Plans routed in set-up because the plan cache lacked them: the
``route/plan`` spans with ``cached`` false before the window. 0 on a warm
plan cache; a cell's first run on a machine routes every one."""
from benchmarks.layer_metrics import _setup

NAME, UNIT, SOURCE = "setup_plans_routed", "count", "program_counter"


def read(context):
    plans = _setup.before_window(context, ("route/plan",))
    if not plans:
        return None
    return sum(1 for s in plans if not s["attrs"].get("cached"))
