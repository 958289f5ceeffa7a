"""Host report: ms per protocol round inside `report` spans over the window
(`FleetServe.report`, with the per-core health sweep `fleet_health`).

A total over the whole window divided by all of its rounds; nothing where
the cell has no `report` span."""


def read(ctx):
    seconds = ctx.spans.get("report")
    if seconds is None:
        return None
    return 1e3 * seconds / ctx.window.rounds
