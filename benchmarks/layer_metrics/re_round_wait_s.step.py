"""Host seconds the adaptive random-effect driver waits for the device: the
``re/round_wait`` spans (the two ``device_get`` calls of every round, which
block until the chunk just dispatched has retired) cut to the window, summed
(they never overlap on a thread), over the steps."""
from benchmarks.layer_metrics import _compile

NAME, UNIT, SOURCE = "re_round_wait_s.step", "s/step", "program_span"


def read(context):
    waits = [s for s in context["spans"] if s["name"] == "re/round_wait"]
    if not waits:
        return None
    return sum(b - a for a, b in _compile.clipped(context, waits)) / context["steps"]
