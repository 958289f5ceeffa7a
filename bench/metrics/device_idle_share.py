"""Device idle share of the traced session, in %: 1 - busy / window.

Busy is the union of the device's program (XLA module) intervals inside the
traced window, which runs from the session's first host span to its last
(`bench.xplane`). Nothing where the trace holds no device program."""


def read(ctx):
    if not ctx.trace or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
