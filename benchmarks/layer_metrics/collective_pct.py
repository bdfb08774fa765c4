"""Share of the devices' busy time spent in collectives: the self time of
the ``all-reduce``, ``all-gather``, ``collective-permute`` and
``reduce-scatter`` operations in the trace (the sums over ``feat`` and over
``data`` of a grid's fixed-effect solve, and whatever else the compiler
placed) over the self time of every device operation, both summed over the
devices. An asynchronous collective counts by its ``-start`` and ``-done``
operations' own time, not by the compute that runs between them. None where
the program's spans name no mesh of more than one device: one chip has no
collective to wait for, and a tree before the spans said ``devices`` is not
read."""
NAME, UNIT, SOURCE = "collective_pct", "%", "device_trace"
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute", "reduce-scatter")


def read(context):
    trace = context["trace"]
    if trace is None:
        return None
    on_mesh = [s for s in context["spans"] if s["name"] == "game/build_coordinate"
               and int(s["attrs"].get("devices") or 1) > 1]
    busy = sum(trace["self_times"].values())
    if not on_mesh or busy <= 0:
        return None
    waited = sum(seconds for name, seconds in trace["self_times"].items()
                 if any(kind in name for kind in COLLECTIVES))
    return 100.0 * waited / busy
