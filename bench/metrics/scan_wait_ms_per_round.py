"""Waiting on the scan: ms per round the host spent in the benchmark's `run`
spans outside the program's `serve/session` spans, over the rounds of those
sessions: the `block_until_ready` on work the session had already queued.
Nothing where the cell has no `run` span or the program records no
`serve/session` span."""
from bench import program_spans


def read(ctx):
    run = ctx.spans.get("run")
    if run is None:
        return None
    return program_spans.ms_per_round(
        lambda spans: run - spans.totals("serve/session"))
