"""Device / compiler: backend compilations (persistent-cache loads
included) inside the measured window; 0 when the warm-up covered every
shape."""


def read(ctx):
    return ctx["compiles"]
