"""Imputation layer: cells the service imputed in the window
(``summary()["imputations"]``) per completed query."""


def read(ctx):
    if not ctx["queries"]:
        return None
    return ctx["imputations"] / ctx["queries"]
