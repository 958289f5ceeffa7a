"""Scan driver: ms per protocol round inside `run` spans over the window
(`ScanEngine.run` ended by `block_until_ready`: state init, host-to-device
copy and the scanned rounds).

A total over the whole window divided by all of its rounds; nothing where
the cell has no `run` span."""


def read(ctx):
    seconds = ctx.spans.get("run")
    if seconds is None:
        return None
    return 1e3 * seconds / ctx.window.rounds
