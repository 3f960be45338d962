"""Backend compiles inside the window that the persistent cache did not
serve, counted by a ``jax.monitoring`` listener.  It should read 0."""


def read(ctx):
    return float(ctx["compiles_in_window"])
