"""device.idle_launch: the share of the traced window in which device 0 ran no
operation while the host was in ``executor.launch``: argument conversion,
host-to-device copies and the jitted calls until they return.  From the
program's spans in the profiler trace (``bench/program_spans.py``)."""

from bench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, "launch")
