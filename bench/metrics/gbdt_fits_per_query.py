"""Imputation layer: XGBoost-hist fits (``gbdt:fit`` spans) per completed
query.  Also logs each query's fits by its index in the window (the query
whose ``query`` span holds the fit), so that two runs can be compared
query by query: a query's fits are set by which attributes it needs
imputed."""


def read(ctx):
    fits = sorted(t0 for name, t0, _t1, _a in ctx["spans"]
                  if name == "gbdt:fit")
    if not fits or not ctx["queries"]:
        return None
    queries = sorted((t0, t1) for name, t0, t1, _a in ctx["spans"]
                     if name == "query")
    per = [sum(1 for t in fits if t0 <= t <= t1) for t0, t1 in queries]
    ctx["log"]("gbdt fits by query: " + ",".join(map(str, per)))
    return len(fits) / ctx["queries"]
