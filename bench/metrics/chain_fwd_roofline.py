"""chain_fwd_roofline: the fused FAµST chain kernel's share of its roofline.

Σ over the chain calls the traced window made (counted from the model calls
the benchmark recorded, only those that dispatch sent to the fused kernel)
of the least time their required work takes on this chip — the larger of
FLOPs over peak and bytes over HBM bandwidth, from the configuration's work
counts — divided by the summed device time of the chain kernel's events."""

from bench.peaks import least_time_s

# The chain kernel's op events as a v5e trace names them: each XLA op event
# carries its HLO instruction text, and a Pallas kernel is a custom call to
# "tpu_custom_call" (named after the enclosing jit, e.g. "%closed_call.26").
# On the serving path the FAµST chain is the only Pallas kernel today; a
# later Pallas kernel on this path needs a stable name= to be told apart.
KERNELS = [r'custom_call_target="tpu_custom_call"']


def _calls(ctx):
    fam, c = ctx.family, ctx.config
    for kind, arg in ctx.calls:
        if kind == "decode":
            yield from fam.chain_calls_decode(c, len(arg))
        else:
            yield from fam.chain_calls_prefill(c, arg)


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    t_kernel = tr.kernel_s(KERNELS)
    if t_kernel <= 0:
        return None
    chains = ctx.family.chains(ctx.config)
    least = 0.0
    for role, rows, n in _calls(ctx):
        rep = ctx.dispatch.get((role, rows))
        if rep is None or rep.backend != "fused":
            continue
        flops, byts = ctx.family.chain_work(chains[role], rows)
        least += n * least_time_s(flops, byts, ctx.peaks, ctx.config["dtype"])[0]
    if least <= 0:
        return None
    return 100.0 * least / t_kernel
