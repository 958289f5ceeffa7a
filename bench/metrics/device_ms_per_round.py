"""Fleet step on the device: ms per round of the scan program's device time.

From the traced session: the union of the scan program's intervals on the
device (`bench.xplane`), over the session's rounds. Nothing where the trace
holds no device program."""


def read(ctx):
    if not ctx.trace or not ctx.trace["scan_module"] or not ctx.traced_rounds:
        return None
    return 1e3 * ctx.trace["scan_busy_s"] / ctx.traced_rounds
