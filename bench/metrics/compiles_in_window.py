"""XLA backend compiles inside the window (`jax.monitoring`), programs
loaded from the persistent compile cache left out (they are
`cache_loads_in_window`); 0 when set-up warmed every program the window
runs."""


def read(ctx):
    return ctx.compiles_in_window
