"""device.idle_engine: the share of the traced window in which device 0 ran no
operation while the host was inside ``engine.step`` but in no ``executor.*``
span: the engine's own scheduling, dispatch query, token append and
completion.  From the program's spans in the profiler trace
(``bench/program_spans.py``)."""

from bench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, "engine")
