"""Host seconds a step spends in the backend's compile step: the union of the
program's ``jit/backend`` spans inside the window, over the steps. For a
program the persistent cache holds this is a load from it; a program that
compiled in under the cache's minimum time (0.5 s: ``utils/cachedir.py``) is
never stored and is compiled again every time it is lowered anew."""
from benchmarks.layer_metrics import _compile

NAME, UNIT, SOURCE = "backend_compile_s.step", "s/step", "program_span"


def read(context):
    return _compile.union_per_step(context, _compile.compile_spans(context, ("jit/backend",)))
