"""Shared by the readers of the factored coordinate's spans
(photon_ml_tpu/algorithm/factored_random_effect.py): ``mf/update`` under the
coordinate's ``cd/coordinate``, and inside it per alternation ``mf/project``,
``mf/solve_latent`` and ``mf/solve_matrix`` (``iterations``, ``evaluations``,
``coefficients``). A program without them (any commit before PR 34) gives
every reader here ``None``."""
from benchmarks.layer_metrics import _spans


def coordinate_spans(context):
    """The window's ``cd/coordinate`` spans that hold an ``mf/update``: the
    factored coordinate's updates, device wait included."""
    inner = _spans.in_window(context, "mf/update")
    return [
        s for s in _spans.in_window(context, "cd/coordinate")
        if any(s["start"] <= u["start"] and u["end"] <= s["end"] for u in inner)
    ]


def matrix_solves(context):
    """The window's ``mf/solve_matrix`` spans that say what the solver
    counted."""
    return [s for s in _spans.in_window(context, "mf/solve_matrix")
            if s["attrs"].get("evaluations") is not None]
