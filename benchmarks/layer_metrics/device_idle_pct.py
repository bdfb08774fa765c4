"""Share of the traced window in which no operation ran on the device: one
minus the union of the device-operation intervals over the window."""
NAME, UNIT, SOURCE = "device_idle_pct", "%", "device_trace"


def read(context):
    trace = context["trace"]
    if trace is None or trace["idle_share"] is None:
        return None
    return 100.0 * trace["idle_share"]
