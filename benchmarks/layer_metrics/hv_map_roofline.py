"""The routed sparse-map kernels' share of their roofline in a solve made of
Hessian-vector products: the least time the chip needs for the maps the
algorithm needs (work.fe_map: the matrix once at 8 bytes a nonzero, the input
vector read, the output written; bytes bound it) over the summed device time
of the routed-map Pallas kernels in the trace (``sparse_map_roofline``'s
kernels, by the same name).

The count is the algorithm's minimum: two maps a value-and-gradient and two
a Hessian-vector product, from the ``evaluations`` and ``hessian_vecs`` of
the window's ``glm/solve`` spans. The time is every call's: a map the
program makes beyond those (``hessian_vec`` computes the margins again at
every product, a third map) is in the time and not in the count, and the
spill side's scatter-add and the maps' XLA prologue are in neither. So the
share reads low, never high. None where the trace names no such kernel or
the spans carry no ``hessian_vecs``."""
from benchmarks import work
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "hv_map_roofline", "%", "device_trace"
KERNELS = "pallas:routed_map_kernel"


def read(context):
    trace, peaks = context["trace"], context["peaks"]
    if trace is None or peaks is None:
        return None
    seconds = trace["self_times"].get(KERNELS)
    solves = [s["attrs"] for s in _spans.in_window(context, "glm/solve")
              if s["attrs"].get("hessian_vecs") is not None
              and s["attrs"].get("evaluations") is not None]
    if not seconds or not solves:
        return None
    maps = sum(2 * (a["evaluations"] + a["hessian_vecs"]) for a in solves)
    shapes = context["shapes"]
    flops, nbytes = work.fe_map(shapes["nnz"], shapes["n_rows"], shapes["n_cols"])
    least, _ = work.least_seconds(maps * flops, maps * nbytes, peaks)
    return 100.0 * least / seconds
