"""Programs loaded from the persistent compile cache inside the window
(`jax.monitoring` cache hits): work a session does each time it traces a
program anew, which `compiles_in_window` does not count."""


def read(ctx):
    return ctx.cache_loads_in_window
