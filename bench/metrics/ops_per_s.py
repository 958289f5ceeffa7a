"""Protocol ops served over the window's wall time, ops/s.

All ops of every session in the window (the one in flight at its end
included) over the time from the first session's start to the last one's
end: no session is left out and none is weighted."""


def read(ctx):
    return ctx.window.ops_per_s
