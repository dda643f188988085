"""ssm_state_roofline: the Mamba-2 state ops' share of their roofline.

Σ over the prefills and decode steps of the traced window of the least time
the state bytes they require take at the chip's HBM bandwidth — each live
row's state read and written once per Mamba layer and decode step, each
layer's final state written once per prefill (the family's
``ssm_state_bytes``) — divided by the device time of the ops that write a
state (``bench/ssm_state.py``)."""

from bench import ssm_state


def read(ctx):
    t = ssm_state.state_seconds(ctx)
    count = getattr(ctx.family, "ssm_state_bytes", None)
    if not t or count is None:
        return None
    byts = sum(count(ctx.config, kind, arg) for kind, arg in ctx.calls)
    return 100.0 * byts / ctx.peaks["hbm_bytes_per_s"] / t
