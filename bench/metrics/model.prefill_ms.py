"""model.prefill_ms: the engine's timed prefill seconds over the prefills
it ran in the traced window (``EngineStats.prefill_s`` / ``admitted``), in ms."""


def read(ctx):
    st = ctx.stats
    if not st["admitted"]:
        return None
    return st["prefill_s"] / st["admitted"] * 1e3
