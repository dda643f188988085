"""model.programs_per_step: device 0's program executions (events of its
``XLA Modules`` line) that start in the traced window, over the
``engine.step`` spans that start in it.  The decode and prefill programs
are two of them; the rest are the small programs of sampling and the NaN
guard."""

from bench import program_spans


def read(ctx):
    pt = program_spans.for_run(ctx)
    if pt is None:
        return None
    return program_spans.programs_per_step(ctx.trace.window, pt)
