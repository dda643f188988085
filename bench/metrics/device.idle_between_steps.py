"""device.idle_between_steps: the share of the traced window in which device 0
ran no operation while the host was in no ``engine.step`` span: the caller's
own code between engine steps (in the benchmark, the load generator's
bookkeeping and the closed loop's resubmits).  From the program's spans in
the profiler trace (``bench/program_spans.py``)."""

from bench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, "between_steps")
