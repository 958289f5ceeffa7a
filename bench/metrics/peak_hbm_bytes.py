"""`peak_bytes_in_use` of the fullest chip, read after the window."""


def read(ctx):
    return ctx.peak_bytes
