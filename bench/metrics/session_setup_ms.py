"""Service layer: milliseconds of ``session_setup`` spans (planning, plan
cache, per-query table snapshots) per completed query."""


def read(ctx):
    spans = [t1 - t0 for name, t0, t1, _a in ctx["spans"]
             if name == "session_setup"]
    if not spans or not ctx["queries"]:
        return None
    return 1e3 * sum(spans) / ctx["queries"]
