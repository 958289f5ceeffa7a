"""Host-to-device copy: ms per round inside the program's `serve/h2d` spans
(the four plan grids' `jnp.asarray`), over the rounds of the traced
window's `serve/session` spans. Nothing where the program records no such
span."""
from bench import program_spans


def read(ctx):
    return program_spans.ms_per_round(
        lambda spans: spans.totals("serve/h2d"))
