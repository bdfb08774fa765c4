"""Host seconds to build the fixed effect's device features: routing (or a
plan-cache read) and upload. The span ``game/build_coordinate`` of the
fixed-effect coordinate where the estimator builds it; the driver's own wall
time around the feature build where there is no estimator."""
NAME, UNIT, SOURCE = "routing_prep_s", "s", "program_span"


def read(context):
    built = [
        s for s in context["spans"]
        if s["name"] == "game/build_coordinate"
        and s["attrs"].get("kind") == "FixedEffectCoordinateConfiguration"
    ]
    if built:
        return sum(s["end"] - s["start"] for s in built)
    return context["times"].get("feature_build_s")
