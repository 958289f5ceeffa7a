"""Scan dispatch: ms per round inside the program's `serve/dispatch` spans
(the call of the jitted scan, which returns once the work is queued), over
the rounds of the traced window's `serve/session` spans. Nothing where the
program records no such span."""
from bench import program_spans


def read(ctx):
    return program_spans.ms_per_round(
        lambda spans: spans.totals("serve/dispatch"))
