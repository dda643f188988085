"""engine.occupancy: mean live batch over the decode steps of the traced
window, from the engine's own ``EngineStats.occupancy`` counter."""


def read(ctx):
    occ = ctx.stats["occupancy"]
    steps = sum(occ.values())
    if not steps:
        return None
    return sum(b * n for b, n in occ.items()) / steps
