"""The Mamba-2 state's ops in a profiler trace, for the ``ssm_state.*``
metrics.

An op event of the ``XLA Ops`` line carries its HLO instruction text, whose
output shape (a tuple's members each) comes before the opcode.  A state op
is one whose output, or a member of its output tuple, ends in the
configuration's ``(heads, head dim, state)`` dims — the family's
``state_dims`` — whatever its leading dims (the slot rows, the stacked
layers, the SSD's chunks).  Ops that hold other ops on the same line
(``while``, ``conditional``, ``call``) are left out: a layer scan's loop
carries the states too.
"""
from __future__ import annotations

import re

from bench.trace_reduce import _HLO, CONTAINERS

_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def is_state_op(text: str, dims: tuple) -> bool:
    m = _HLO.match(text)
    if not m or m.group(3) in CONTAINERS:
        return False
    n = len(dims)
    for shape in _SHAPE.findall(m.group(2)):
        got = tuple(int(x) for x in shape.split(",") if x)
        if got[-n:] == tuple(dims):
            return True
    return False


def state_seconds(ctx) -> float | None:
    """Device seconds of the state ops in the traced window, averaged over
    the devices; None where the family has no Mamba-2 state or the trace
    no op."""
    tr = ctx.trace
    dims_of = getattr(ctx.family, "state_dims", None)
    if tr is None or dims_of is None or not tr.ops:
        return None
    dims = dims_of(ctx.config)
    w0, w1 = tr.window
    tot = 0
    for dev in tr.ops:
        for text, a, b in dev:
            if is_state_op(text, dims):
                tot += max(0, min(b, w1) - max(a, w0))
    return tot / len(tr.ops) * 1e-9
