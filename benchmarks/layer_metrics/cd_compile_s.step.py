"""Compile seconds (trace, lowering, backend) caused by the coordinate-descent
driver and the estimator around it (scoring, score plane, objective,
validation, set-up of a fit): the union of the program's ``jit/*`` spans
inside the window recorded under neither ``glm/train`` nor ``re/train``,
over the steps. None where no coordinate descent runs."""
from benchmarks.layer_metrics import _compile

NAME, UNIT, SOURCE = "cd_compile_s.step", "s/step", "program_span"


def read(context):
    if not any(s["name"] == "cd/outer_iter" for s in context["spans"]):
        return None
    spans = _compile.compile_spans(context, not_under=("glm/train", "re/train"))
    return _compile.union_per_step(context, spans)
