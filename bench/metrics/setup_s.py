"""Seconds from process start to the first timed session: imports, the
compile (or compile-cache load), state init, one warm session and the
tapes the window will run."""


def read(ctx):
    return ctx.setup_s
