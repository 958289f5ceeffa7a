"""Waiting on a segmented scan: ms per round the host spent in the
benchmark's `run` spans outside the program's `serve/segment` spans, over
the rounds of those segments: the device copies and the
`block_until_ready` on work already queued. The segmented path's
`scan_wait_ms_per_round`. Nothing where the cell has no `run` span or the
program records no `serve/segment` span."""


def read(ctx):
    run = ctx.spans.get("run")
    if run is None:
        return None
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    rounds = spans.count("serve/segment", "rounds")
    if not rounds:
        return None
    return 1e3 * (run - spans.totals("serve/segment")) / rounds
