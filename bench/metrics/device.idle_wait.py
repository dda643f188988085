"""device.idle_wait: the share of the traced window in which device 0 ran no
operation while the host was in ``executor.wait``: blocked on a step's
logits, so the gap lies inside or before a program.  From the program's
spans in the profiler trace (``bench/program_spans.py``)."""

from bench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, "wait")
