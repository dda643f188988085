"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* Device busy time is the union of the intervals in which an operation ran
  on the device (the ``XLA Ops`` line of each ``/device:`` plane), clipped to
  the traced window and averaged over the devices.
* Kernel time is the summed duration of the op events whose names match a
  metric's list of kernel names.
* Idle gaps are the stretches of the window with no op running; each is
  named after the ``bench.*`` host span that covers most of it.

The traced window is the ``bench.window`` host span; host and device events
of one trace share one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OP_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# ops that contain other ops on the same line: left out of the op ranking
CONTAINERS = ("while", "conditional", "call")
_HLO = re.compile(r"(%?[\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(")


def short_name(text: str) -> tuple[str, str]:
    """``("%closed_call.26 custom-call bf16[128,13696]", "custom-call")`` from
    an op event's HLO instruction text."""
    m = _HLO.match(text)
    if not m:
        return text[:120], ""
    shape = m.group(2)
    shape = shape.split("{")[0] if not shape.startswith("(") else "(tuple)"
    return f"{m.group(1)} {m.group(3)} {shape}", m.group(3)


@dataclasses.dataclass
class Trace:
    ops: list  # per device: [(name, start_ns, end_ns)] sorted by start
    spans: list  # [(name, start_ns, end_ns)] bench.* host spans
    window: tuple  # (start_ns, end_ns)
    planes: dict  # plane name -> {line name: event count}

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy_intervals(self, dev: int) -> list:
        w0, w1 = self.window
        merged: list = []
        for _, a, b in self.ops[dev]:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return merged

    @property
    def busy_s(self) -> float:
        if not self.ops:
            return 0.0
        tot = sum(sum(b - a for a, b in self._busy_intervals(d)) for d in range(len(self.ops)))
        return tot / len(self.ops) * 1e-9

    def kernel_s(self, names) -> float:
        """Device seconds of the op events whose name matches any of the
        regular expressions ``names``, averaged over the devices."""
        if not self.ops:
            return 0.0
        pat = re.compile("|".join(f"(?:{n})" for n in names))
        w0, w1 = self.window
        tot = 0
        for dev in self.ops:
            for name, a, b in dev:
                if pat.search(name):
                    tot += max(0, min(b, w1) - max(a, w0))
        return tot / len(self.ops) * 1e-9

    def idle_gaps(self, dev: int = 0) -> list:
        """``(start_ns, end_ns)`` of each stretch of the window with no op."""
        if not self.ops:
            return []
        gaps, t = [], self.window[0]
        for a, b in self._busy_intervals(dev):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if self.window[1] > t:
            gaps.append((t, self.window[1]))
        return gaps

    def span_at(self, a: int, b: int) -> str:
        """The innermost ``bench.*`` span that covers most of ``[a, b)``."""
        best, best_cover, best_len = "none", 0, None
        for name, s, e in self.spans:
            if name == WINDOW_SPAN:
                continue
            cover = min(b, e) - max(a, s)
            if cover <= 0:
                continue
            if cover > best_cover or (cover == best_cover and (e - s) < best_len):
                best, best_cover, best_len = name, cover, e - s
        return best

    def breakdown(self, top: int = 10) -> dict:
        by_name: dict = {}
        w0, w1 = self.window
        for text, a, b in (self.ops[0] if self.ops else []):
            name, kind = short_name(text)
            d = max(0, min(b, w1) - max(a, w0))
            if d and kind not in CONTAINERS:
                by_name[name] = by_name.get(name, 0) + d
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: -(g[1] - g[0]))[:top]
        return {
            "device_ops": [[n, d * 1e-9] for n, d in ops],
            "idle_gaps": [[self.span_at(a, b), (b - a) * 1e-9] for a, b in gaps],
        }

    def summary(self) -> str:
        return (f"window {self.window_s:.6f}s busy {self.busy_s:.6f}s devices "
                f"{len(self.ops)} op events {sum(len(d) for d in self.ops)} "
                f"spans {len(self.spans)}")


def reduce_file(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans, planes = [], [], {}
    for plane in pd.planes:
        lines = {}
        is_device = plane.name.startswith("/device:")
        dev_ops = []
        for line in plane.lines:
            n = 0
            for ev in line.events:
                n += 1
                a = int(ev.start_ns)
                b = a + int(ev.duration_ns)
                if is_device and line.name == OP_LINE:
                    dev_ops.append((ev.name, a, b))
                elif not is_device and ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, a, b))
            lines[line.name] = n
        planes[plane.name] = lines
        if is_device and dev_ops:
            dev_ops.sort(key=lambda e: e[1])
            ops.append(dev_ops)
    windows = [(a, b) for name, a, b in spans if name == WINDOW_SPAN]
    if windows:
        window = max(windows, key=lambda w: w[1] - w[0])
    else:
        starts = [e[1] for d in ops for e in d]
        ends = [e[2] for d in ops for e in d]
        window = (min(starts), max(ends)) if starts else (0, 0)
    return Trace(ops, spans, window, planes)


def find_trace(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def reduce_dir(log_dir: str) -> Trace:
    return reduce_file(find_trace(log_dir))
