"""Host seconds, device wait included, of the feature statistics a
normalization is built from: the ``glm/summarize`` spans (``stat.summarize``,
in set-up: before the window). None where the program opens no such span
(any commit before PR 28) or the cell asks for no statistics."""
NAME, UNIT, SOURCE = "summarize_s", "s", "program_span"


def read(context):
    spans = [s for s in context["spans"] if s["name"] == "glm/summarize"]
    return sum(s["end"] - s["start"] for s in spans) if spans else None
