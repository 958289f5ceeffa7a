"""Relocated reallocs, in %: the `moved` answers the program's `serve/readback`
spans counted over the `reallocs` its `serve/segment` and `serve/session`
spans counted, in the traced window's span log. Nothing where the program
records no such count or the window served no realloc."""


def read(ctx):
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    reallocs = (spans.count("serve/segment", "reallocs")
                + spans.count("serve/session", "reallocs"))
    if not reallocs:
        return None
    return 100.0 * spans.count("serve/readback", "moved") / reallocs
