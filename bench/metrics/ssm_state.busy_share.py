"""ssm_state.busy_share: device time of the ops that write a Mamba-2 state
(an output, or a member of an output tuple, whose trailing dims are the
configuration's ``(heads, head dim, state)``; ``bench/ssm_state.py``) over
all device busy time in the traced window."""

from bench import ssm_state


def read(ctx):
    t = ssm_state.state_seconds(ctx)
    if not t:
        return None
    busy = ctx.trace.busy_s
    return 100.0 * t / busy if busy > 0 else None
