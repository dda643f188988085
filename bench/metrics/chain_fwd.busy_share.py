"""chain_fwd.busy_share: device time of the fused FAµST chain kernel
(``kernels/chain.py``) over all device busy time in the traced window."""

# The chain kernel's op events as a v5e trace names them: each XLA op event
# carries its HLO instruction text, and a Pallas kernel is a custom call to
# "tpu_custom_call" (named after the enclosing jit, e.g. "%closed_call.26").
# On the serving path the FAµST chain is the only Pallas kernel today; a
# later Pallas kernel on this path needs a stable name= to be told apart.
KERNELS = [r'custom_call_target="tpu_custom_call"']


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    busy = tr.busy_s
    t = tr.kernel_s(KERNELS)
    if busy <= 0 or t <= 0:
        return None
    return 100.0 * t / busy
