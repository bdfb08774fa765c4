"""Programs a step compiled and wrote to the persistent cache because it did
not hold them: the program's ``jit/cache`` spans with ``hit=False`` inside the
window, over the steps. 0 on a warm checkout. JAX reports a miss only for a
program it then stores, so the small programs under the cache's minimum
compile time, compiled every time, are not in this count: they are in
``backend_compile_s.step``."""
from benchmarks.layer_metrics import _compile

NAME, UNIT, SOURCE = "cache_misses.step", "count/step", "program_counter"


def read(context):
    if not _compile.has_compile_spans(context):
        return None
    lo, hi = context["window"]
    misses = [s for s in _compile.compile_spans(context, ("jit/cache",))
              if lo <= s["start"] <= hi and not s["attrs"].get("hit")]
    return len(misses) / context["steps"]
