"""Fixed-effect solver iterations in the window, from the solver's own
tracker, over the steps. The tracker counts iterations, not evaluations:
each iteration is at least one value-and-gradient, line-search retries on
top are not counted by the program."""
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "fe_iterations.step", "count/step", "program_counter"


def read(context):
    counts = [c["fe_iterations"] for c in _spans.window_counters(context)
              if c.get("fe_iterations") is not None]
    return sum(counts) / context["steps"] if counts else None
