"""The Kronecker maps' share of their roofline: the least time the chip
needs for the window's matrix-solve evaluations (work_mf.kron_evaluation:
two maps an evaluation, each reading the ``[E, S, D]`` block and the gathered
rows of the projection matrix once; bytes bound it) over the summed
``mf/solve_matrix`` span time, which waits for the solve's device work.

The count is the solver's own (``evaluations`` of the window's
``mf/solve_matrix`` spans); the time is the whole solve's: the optimizer's
vector work, the scatter-add of the gradient and the dispatch are in the time
and not in the count, so the share reads low, never high. The maps are XLA
fusions with no kernel name of their own, so there is no device-trace reading
beside this one. None where the program makes no such span, where the driver
gives no bucket shapes, or on a rehearsal (no peaks)."""
from benchmarks import work, work_mf
from benchmarks.layer_metrics import _mf, _spans

NAME, UNIT, SOURCE = "kron_map_roofline", "%", "program_span"


def read(context):
    peaks, shapes = context["peaks"], context["shapes"]
    solves = _mf.matrix_solves(context)
    if peaks is None or not solves or not shapes.get("mf_buckets"):
        return None
    seconds = _spans.seconds(solves)
    evaluations = sum(int(s["attrs"]["evaluations"]) for s in solves)
    if not seconds or not evaluations:
        return None
    flops, nbytes = work_mf.kron_evaluation(shapes["mf_buckets"], shapes["mf_latent_factors"])
    least, _ = work.least_seconds(evaluations * flops, evaluations * nbytes, peaks)
    return 100.0 * least / seconds
