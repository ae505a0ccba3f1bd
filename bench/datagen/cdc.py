"""CDC-NHANES-shaped tables (QUIP paper, section 7.1; ImputeDB, SIGMOD 2017):
demo, labs, exams, joined 1:1 on ``id``.

A copy of the shapes of ``repro.data.synthetic.cdc_dataset``: nine numeric
attributes per table plus ``id``, each correlated with one latent health
factor per participant, rounded to 0.1.  Every attribute misses exactly
``round(rate * rows)`` cells, drawn without replacement, so that each seed
gives the imputer the same reference-row counts.  Tables are plain dicts as
in ``wifi.py``; missing cells hold 0.0.
"""

from __future__ import annotations

import numpy as np

from masks import exact_mask

__all__ = ["make"]


def make(rng: np.random.Generator, p: dict) -> dict:
    sizes = {"demo": p["n_demo"], "labs": p["n_labs"], "exams": p["n_exams"]}
    latent = rng.normal(0, 1, p["n_demo"])
    tables = {}
    for t, n in sizes.items():
        ids = np.arange(n, dtype=np.int64)
        cols = {f"{t}.id": ids}
        missing = {f"{t}.id": np.zeros(n, dtype=bool)}
        columns = [(f"{t}.id", "int")]
        for attr in p["attributes"][t]:
            name = f"{t}.{attr}"
            vals = np.round(rng.normal(50, 10, n) + 12.0 * latent[:n]
                            + rng.normal(0, 3, n), 1)
            m = exact_mask(rng, n, p["missing_rates"].get(name, 0.0))
            cols[name] = np.where(m, 0.0, vals)
            missing[name] = m
            columns.append((name, "float"))
        tables[t] = {"columns": columns, "cols": cols, "missing": missing}
    return tables
