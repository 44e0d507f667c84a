"""Backend compilations JAX reported during the measured window."""


def read(ctx):
    return ctx["compiles_in_window"]
