"""device.idle_readback: the share of the traced window in which device 0 ran
no operation while the host was in ``executor.sample`` or
``executor.row_finite``: the argmax and the NaN guard, each a few small
programs and a copy to the host.  From the program's spans in the profiler
trace (``bench/program_spans.py``)."""

from bench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, "readback")
