"""Share of the random effects' ``[E, S, D]`` block cells that hold a real
value: ``real_cells`` (an active row's real local columns) over
``padded_cells`` (every cell of every bucket), summed over the
``re/build_dataset`` spans of set-up, every coordinate's. None where no span
carries them (a program older than these attrs, or a cell with no random
effect)."""
from benchmarks.layer_metrics import _setup

NAME, UNIT, SOURCE = "re_fill_pct", "%", "program_counter"


def read(context):
    spans = [s["attrs"] for s in _setup.before_window(context, ("re/build_dataset",))
             if s["attrs"].get("padded_cells")]
    if not spans:
        return None
    return 100.0 * sum(a["real_cells"] for a in spans) / sum(a["padded_cells"] for a in spans)
