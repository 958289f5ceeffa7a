"""Host planner: ms per protocol round inside `plan` spans over the window
(`FleetServe.plan`, the serving tier's `SessionPlanner`).

A total over the whole window divided by all of its rounds; nothing where
the cell has no `plan` span."""


def read(ctx):
    seconds = ctx.spans.get("plan")
    if seconds is None:
        return None
    return 1e3 * seconds / ctx.window.rounds
