"""Programs set-up compiled and wrote to the persistent cache because it
did not hold them: the ``jit/cache`` spans with ``hit`` false that start
before the window. 0 on a warm compile cache; it tells a run that met a cold
cache from a warm one. None where the program makes no compile spans."""
from benchmarks.layer_metrics import _compile, _setup

NAME, UNIT, SOURCE = "setup_cache_misses", "count", "program_counter"


def read(context):
    if not _compile.has_compile_spans(context):
        return None
    return sum(1 for s in _setup.before_window(context, ("jit/cache",))
               if not s["attrs"].get("hit"))
