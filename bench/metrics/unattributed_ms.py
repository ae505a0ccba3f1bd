"""Service layer: milliseconds per completed query inside ``query`` spans
(submit to finalize) that none of the program's layer spans cover: what
the spans cannot explain yet."""

from intervals import covered_minus

LAYERS = ("session_setup", "op:select", "op:join_build", "op:join_probe",
          "kernel:multi_match", "op:rho", "op:finalize", "impute_flush",
          "compiled_exec")


def read(ctx):
    queries = [(t0, t1) for name, t0, t1, _a in ctx["spans"]
               if name == "query"]
    if not queries or not ctx["queries"]:
        return None
    layers = [(t0, t1) for name, t0, t1, _a in ctx["spans"] if name in LAYERS]
    return 1e3 * covered_minus(queries, layers) / ctx["queries"]
