"""model.decode_step_ms: the engine's timed decode seconds over its decode
steps in the traced window (``EngineStats.decode_s`` / ``steps``), in ms."""


def read(ctx):
    st = ctx.stats
    if not st["steps"]:
        return None
    return st["decode_s"] / st["steps"] * 1e3
