"""Host seconds a step spends tracing Python to jaxprs: the union of the
program's ``jit/trace`` spans inside the window, over the steps. A trace
that runs inside a lowering is counted with the lowering."""
from benchmarks.layer_metrics import _compile

NAME, UNIT, SOURCE = "retrace_s.step", "s/step", "program_span"


def read(context):
    return _compile.union_per_step(context, _compile.compile_spans(context, ("jit/trace",)))
