"""Device / compiler: compiles from the first query's submit to the last
query's end that loaded their executable from the persistent compile cache
(``jax:compile`` spans with ``cache_load``).  ``compiles_in_window`` counts
them too: true compiles are its value less this one.  Nothing when the
program recorded no compile at all, set-up included."""


def read(ctx):
    compiles = [(t1, a) for name, _t0, t1, a in ctx["spans"]
                if name == "jax:compile"]
    queries = [(t0, t1) for name, t0, t1, _a in ctx["spans"]
               if name == "query"]
    if not compiles or not queries:
        return None
    lo = min(t0 for t0, _t1 in queries)
    hi = max(t1 for _t0, t1 in queries)
    return sum(1 for t1, a in compiles if a.get("cache_load") and lo <= t1 <= hi)
