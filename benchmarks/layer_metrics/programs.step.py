"""Backend-compile events inside the measured window (a hit in the
persistent cache still counts: the program was traced and lowered anew),
over the steps."""
NAME, UNIT, SOURCE = "programs.step", "count/step", "program_counter"


def read(context):
    lo, hi = context["window"]
    return context["compile"].window(lo, hi)[1] / context["steps"]
