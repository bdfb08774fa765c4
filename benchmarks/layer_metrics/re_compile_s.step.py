"""Compile seconds (trace, lowering, backend) caused by the random-effect
solves: the union of the program's ``jit/*`` spans inside the window that
were recorded under a ``re/train`` span, over the steps. None where no
random effect trains."""
from benchmarks.layer_metrics import _compile

NAME, UNIT, SOURCE = "re_compile_s.step", "s/step", "program_span"


def read(context):
    if not any(s["name"] == "re/train" for s in context["spans"]):
        return None
    return _compile.union_per_step(context, _compile.compile_spans(context, under="re/train"))
