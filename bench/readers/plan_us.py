"""Planner: mean ``plan`` span per request, in microseconds."""


def read(ctx):
    spans = [s for s in ctx["spans"] or () if s.name == "plan"]
    if not spans:
        return None
    return sum(s.duration_us for s in spans) / len(spans)
