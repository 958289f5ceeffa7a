"""State init: ms per round inside the program's `serve/init` spans
(`heap.sharded_init`: the per-session retrace of its prepopulating scan,
its load from the compile cache and the eager init dispatch), their child
spans left out, over the rounds of the traced window's `serve/session`
spans. Nothing where the program records no such span."""
from bench import program_spans


def read(ctx):
    return program_spans.ms_per_round(
        lambda spans: spans.self_time("serve/init"))
