"""The program's own span log (`repro.runtime.spans`), read after a traced
window by the scan driver's per-layer metrics.

The serving path records `serve/session` around `serve/init`, `serve/h2d`
and `serve/dispatch` while a profiler capture runs; the window is the only
capture of a run, so the log holds the window's sessions. A program that
has no such log, or a log with no `serve/session` span, gives nothing.
"""


def ms_per_round(seconds):
    """1e3 * `seconds(spans)` over the rounds counted on the log's
    `serve/session` spans; None where there are none."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    rounds = spans.count("serve/session", "rounds")
    if not rounds:
        return None
    return 1e3 * seconds(spans) / rounds
