"""step_mfu: the whole model step's share of the chip's roofline.

Σ over the prefills and decode steps of the traced window of the least time
the configuration's required work takes (every weight, each live row's own
cached keys and values, attention FLOPs included; the larger of FLOPs over
peak and bytes over HBM bandwidth) divided by the host-clock wall time of
the engine steps that ran them."""

from bench.peaks import least_time_s


def read(ctx):
    if ctx.step_wall_s <= 0 or not ctx.calls:
        return None
    fam, c = ctx.family, ctx.config
    least = 0.0
    for kind, arg in ctx.calls:
        if kind == "decode":
            flops, byts = fam.decode_work(c, arg)
        else:
            flops, byts = fam.prefill_work(c, arg)
        least += least_time_s(flops, byts, ctx.peaks, c["dtype"])[0]
    return 100.0 * least / ctx.step_wall_s
