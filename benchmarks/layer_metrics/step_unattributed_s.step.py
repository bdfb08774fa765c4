"""Host seconds of a step that no layer's span covers: the window less the
union of ``game/prepare_fit``, ``cd/initial_scores``, ``cd/coordinate``,
``cd/objective``, ``cd/validate`` and ``glm/train`` inside it, over the
steps. None where the program opens no ``glm/train`` span (before PR 26)."""
from benchmarks.layer_metrics import _compile

NAME, UNIT, SOURCE = "step_unattributed_s.step", "s/step", "program_span"
COVERING = ("game/prepare_fit", "cd/initial_scores", "cd/coordinate", "cd/objective",
            "cd/validate", "glm/train")


def read(context):
    if not any(s["name"] == "glm/train" for s in context["spans"]):
        return None
    covering = [s for s in context["spans"] if s["name"] in COVERING]
    covered = _compile.union_seconds(_compile.clipped(context, covering))
    return (context["window_s"] - covered) / context["steps"]
