"""Share of the dispatched random-effect lane-iterations that advanced a
live entity (``SolverStats``: executed less wasted, over executed), over
the window's steps."""
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "re_live_lane_pct", "%", "program_counter"


def read(context):
    lanes = _spans.window_lanes(context)
    executed = sum(lane["executed"] for lane in lanes)
    if not executed:
        return None
    return 100.0 * sum(lane["live"] for lane in lanes) / executed
