"""Share of the dispatched lane-iterations of the factored coordinate's
latent solves that advanced a live entity (``SolverStats``: executed less
wasted, over executed; the driver's ``re_lanes`` counters of the coordinate
whose ``cd/coordinate`` spans hold an ``mf/update``), over the window's
steps."""
from benchmarks.layer_metrics import _mf, _spans

NAME, UNIT, SOURCE = "mf_latent_live_lane_pct", "%", "program_counter"


def read(context):
    names = {s["attrs"].get("coordinate") for s in _mf.coordinate_spans(context)}
    lanes = [lane for lane in _spans.window_lanes(context) if lane["coordinate"] in names]
    executed = sum(lane["executed"] for lane in lanes)
    if not executed:
        return None
    return 100.0 * sum(lane["live"] for lane in lanes) / executed
