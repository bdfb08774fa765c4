"""Host seconds in the random-effect coordinates' updates: the
``cd/coordinate`` spans of the window whose coordinate sent solver-stats
events (only random effects do), over the steps."""
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "re_solve_s.step", "s/step", "program_span"


def read(context):
    names = {lane["coordinate"] for c in context["counters"] for lane in c.get("re_lanes", [])}
    spans = [s for s in _spans.in_window(context, "cd/coordinate")
             if s["attrs"].get("coordinate") in names]
    return _spans.seconds(spans) / context["steps"] if spans else None
