"""Readback: ms per protocol round inside `readback` spans over the window
(`serving.response_host`, one device-to-host copy per answer field).

A total over the whole window divided by all of its rounds; nothing where
the cell has no `readback` span."""


def read(ctx):
    seconds = ctx.spans.get("readback")
    if seconds is None:
        return None
    return 1e3 * seconds / ctx.window.rounds
