"""Service layer: milliseconds of ``session:snapshot`` spans (the per-query
copies of the tables a query reads, inside ``session_setup``) per completed
query."""

from intervals import covered


def read(ctx):
    spans = [(t0, t1) for name, t0, t1, _a in ctx["spans"]
             if name == "session:snapshot"]
    if not spans or not ctx["queries"]:
        return None
    return 1e3 * covered(spans) / ctx["queries"]
