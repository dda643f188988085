"""The program's own host spans and device 0's program executions, read from
the profiler trace of a traced run.

The serving engine marks its work with ``engine.*`` and ``executor.*`` host
spans (``src/repro/runtime/engine.py``, "Tracing"); they share the trace's
clock with the device's op events.  Each idle gap of device 0
(``Trace.idle_gaps``) is split instant by instant by what the host was doing:

* ``between_steps`` — in no ``engine.step`` (the caller's own code);
* ``engine`` — in ``engine.step`` but in no ``executor.*`` span;
* ``launch`` — in ``executor.launch`` (or in ``executor.prefill`` /
  ``executor.decode`` outside their two children);
* ``wait`` — in ``executor.wait``;
* ``readback`` — in ``executor.sample`` or ``executor.row_finite``.

The innermost open span decides.  Program executions are the events of
device 0's ``XLA Modules`` line.  A trace without these spans, such as one
from a program that has none, reads as nothing.
"""
from __future__ import annotations

import dataclasses
import os

from bench import trace_reduce

PREFIXES = ("engine.", "executor.")
STEP = "engine.step"
MODULE_LINE = "XLA Modules"
PARTS = ("between_steps", "engine", "launch", "wait", "readback")
_PART_OF = {
    "executor.prefill": "launch",
    "executor.decode": "launch",
    "executor.launch": "launch",
    "executor.wait": "wait",
    "executor.sample": "readback",
    "executor.row_finite": "readback",
}


@dataclasses.dataclass
class ProgramTrace:
    spans: list  # [(name, start_ns, end_ns, {arg: value})] program spans, by start
    modules: list  # [(name, start_ns, end_ns)] device 0's program executions, by start

    def named(self, name: str) -> list:
        return [s for s in self.spans if s[0] == name]


def load(path: str) -> ProgramTrace:
    """The program spans of every host thread and the ``XLA Modules`` events
    of device 0 (the first device plane with op events, as in
    ``trace_reduce``)."""
    from jax.profiler import ProfileData

    spans, modules, found_device = [], [], False
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            if found_device:
                continue
            lines = {line.name: line for line in plane.lines}
            ops = lines.get(trace_reduce.OP_LINE)
            if ops is None or next(iter(ops.events), None) is None:
                continue
            found_device = True
            if MODULE_LINE in lines:
                for ev in lines[MODULE_LINE].events:
                    a = int(ev.start_ns)
                    modules.append((ev.name, a, a + int(ev.duration_ns)))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    a = int(ev.start_ns)
                    spans.append((ev.name, a, a + int(ev.duration_ns), {k: v for k, v in ev.stats}))
    spans.sort(key=lambda s: (s[1], -s[2]))
    modules.sort(key=lambda m: m[1])
    return ProgramTrace(spans, modules)


_LOADED: dict = {}


def for_run(ctx) -> ProgramTrace | None:
    """The program trace of the traced run whose metrics ``ctx`` holds, read
    once per trace file; None without a device trace or without program
    spans."""
    from bench import harness

    if ctx.trace is None or not ctx.trace.ops:
        return None
    try:
        path = trace_reduce.find_trace(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = load(path)
    pt = _LOADED[key]
    return pt if pt.named(STEP) else None


def _part(open_spans: list) -> str:
    if not any(s[0] == STEP for s in open_spans):
        return "between_steps"
    inner = max(open_spans, key=lambda s: (s[1], -s[2]))
    return _PART_OF.get(inner[0], "engine")


def pieces(spans: list) -> list:
    """The time the spans cover cut into ``(start_ns, end_ns, part)`` pieces,
    in order and not overlapping, each labelled by the spans open across it."""
    events = []
    for i, (_, a, b, _) in enumerate(spans):
        if b > a:
            events += [(a, 1, i), (b, 0, i)]
    events.sort()
    out, open_, t_prev = [], [], None
    for t, is_start, i in events:
        if open_ and t > t_prev:
            out.append((t_prev, t, _part([spans[j] for j in open_])))
        if is_start:
            open_.append(i)
        else:
            open_.remove(i)
        t_prev = t
    return out


def idle_split(gaps: list, spans: list) -> dict:
    """Nanoseconds of the idle ``gaps`` (sorted, not overlapping) in each of
    ``PARTS``; they sum to the gaps' total."""
    out = dict.fromkeys(PARTS, 0)
    cut = pieces(spans)
    j = 0
    for a, b in gaps:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        covered, k = 0, j
        while k < len(cut) and cut[k][0] < b:
            d = min(b, cut[k][1]) - max(a, cut[k][0])
            if d > 0:
                out[cut[k][2]] += d
                covered += d
            k += 1
        out["between_steps"] += (b - a) - covered
    return out


def idle_share(ctx, part: str) -> float | None:
    """Percent of the traced window in which device 0 was idle while the host
    was in ``part``."""
    pt = for_run(ctx)
    if pt is None or ctx.trace.window_s <= 0:
        return None
    split = idle_split(ctx.trace.idle_gaps(), pt.spans)
    return 100.0 * split[part] * 1e-9 / ctx.trace.window_s


def programs_per_step(window: tuple, pt: ProgramTrace) -> float | None:
    """Device 0's program executions that start in ``window`` over the
    ``engine.step`` spans that start in it."""
    w0, w1 = window
    steps = sum(w0 <= a < w1 for _, a, _, _ in pt.named(STEP))
    programs = sum(w0 <= a < w1 for _, a, _ in pt.modules)
    if not steps or not programs:
        return None
    return programs / steps
