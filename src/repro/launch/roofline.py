"""Roofline analysis from compiled dry-run artifacts.

``collective_stats(hlo_text)`` parses the post-SPMD optimized HLO and sums
the *result* bytes of every collective op, resolving ``while`` trip counts
(layer scans, flash-attention chunk scans) so per-iteration collectives are
multiplied out. ``roofline_terms`` converts a dry-run record into the three
spec-mandated terms:

    compute    = HLO_FLOPs / (chips × 197e12)          [bf16 peak / chip]
    memory     = HLO_bytes / (chips × 819e9)           [HBM BW / chip]
    collective = collective_bytes / (chips × 50e9)     [ICI link BW]

Notes recorded alongside the numbers:
  * cost_analysis flops/bytes are whole-program totals as XLA reports them
    on the CPU backend (per-device program); we scale per-device terms by
    the device count where appropriate;
  * conditionals (gemma3's local/global branches never appear — patterns
    are static) — conditionals if present are counted max-branch.

Peak constants: builtin per-chip numbers keyed by ``device_kind`` (an
unknown TPU kind raises; off-TPU the reference v5e is priced), replaced by
*measured* values when ``scripts/calibrate_roofline.py`` has cached a
``roofline.json`` for this host (``~/.cache/repro/roofline.json``;
``REPRO_ROOFLINE`` overrides the path, ``REPRO_ROOFLINE=builtin`` forces
the defaults).  Live consumers (the dispatch cost model, the autotuner)
go through :func:`roofline_constants`, which re-reads the cache whenever
the configured path or its mtime changes — so a calibration written
mid-process, or a ``REPRO_ROOFLINE`` flip after first import, takes
effect on the next decision instead of being silently ignored.
:func:`reload` forces a re-read.  The module-level ``PEAK_FLOPS`` /
``HBM_BW`` / ``LINK_BW`` are the reference chip's builtin numbers, kept
for static consumers (``launch/report``); anything that prices a live
decision uses the accessor.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
from collections import defaultdict

import numpy as np

# Builtin per-chip constants, keyed by ``jax.Device.device_kind``.  Peaks
# from the Google Cloud TPU documentation ("TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s HBM); ``link_bw`` and ``t_launch_us`` are model constants that
# no chip run has measured yet.
_BUILTIN_BY_KIND = {
    "TPU v5 lite": {
        "peak_flops": 197e12,  # bf16 / chip
        "hbm_bw": 819e9,  # bytes/s / chip
        "link_bw": 50e9,  # bytes/s / link (ICI)
        "t_launch_us": 2.0,  # fixed per-launch overhead (µs)
    },
}
# Off-TPU (CPU tests, compile rehearsals) dispatch prices the chip the
# repo deploys on, so its decisions stay a pure function of the shapes.
_REFERENCE_KIND = "TPU v5 lite"
_BUILTIN = _BUILTIN_BY_KIND[_REFERENCE_KIND]


def builtin_constants() -> dict:
    """Builtin constants for the default device: its ``device_kind`` entry
    on a TPU — a TPU kind missing from the table raises rather than being
    priced as another chip — and the reference chip's off-TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return dict(_BUILTIN)
    if dev.device_kind not in _BUILTIN_BY_KIND:
        raise KeyError(
            f"no builtin roofline constants for TPU kind {dev.device_kind!r}; "
            f"known kinds: {sorted(_BUILTIN_BY_KIND)}"
        )
    return dict(_BUILTIN_BY_KIND[dev.device_kind])


def roofline_cache_path() -> str:
    """Where calibration results live (shared with the calibrate script)."""
    return os.environ.get(
        "REPRO_ROOFLINE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro", "roofline.json"),
    )


def load_roofline() -> tuple[dict, str]:
    """(constants dict, source) — measured values from the calibration
    cache when present and sane, the device's builtin constants otherwise
    (:func:`builtin_constants`).  Unknown/invalid keys fall back
    individually, so a partial cache still contributes what it measured."""
    builtin = builtin_constants()
    path = roofline_cache_path()
    if path.lower() in ("", "0", "builtin", "off"):
        return builtin, "builtin"
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        if not isinstance(data, dict):
            return builtin, "builtin"
        measured = {
            k: float(data[k])
            for k in builtin
            if isinstance(data.get(k), (int, float)) and float(data[k]) > 0
        }
        if not measured:
            return builtin, "builtin"
        return {**builtin, **measured}, f"measured:{path}"
    except (OSError, ValueError):
        return builtin, "builtin"


# Live-state cache for :func:`roofline_constants`: (path, mtime_ns) of the
# last load, so both a REPRO_ROOFLINE flip and an in-place calibration
# rewrite invalidate it without an explicit reload() call.
_STATE: dict = {"stamp": None, "values": None, "source": None}


def _cache_stamp() -> tuple:
    path = roofline_cache_path()
    if path.lower() in ("", "0", "builtin", "off"):
        return (path, None)
    try:
        return (path, os.stat(path).st_mtime_ns)
    except OSError:
        return (path, None)


def roofline_constants() -> tuple[dict, str]:
    """Reloadable accessor: (constants dict, source), re-read whenever the
    configured cache path or the file behind it changes.  This is what the
    dispatch cost model prices with — a calibration written by
    ``scripts/calibrate_roofline.py`` in this same process is picked up on
    the next decision, and ``DispatchReport.roofline`` names the source
    that actually priced it."""
    stamp = _cache_stamp()
    if _STATE["stamp"] != stamp:
        _STATE["values"], _STATE["source"] = load_roofline()
        _STATE["stamp"] = stamp
    return dict(_STATE["values"]), _STATE["source"]


def reload() -> tuple[dict, str]:
    """Drop the cached constants and re-read the calibration file now."""
    _STATE["stamp"] = None
    return roofline_constants()


# Reference-chip snapshots for static consumers (``launch/report``'s
# dry-run report, which models a v5e mesh); live pricing uses the accessor.
PEAK_FLOPS = _BUILTIN["peak_flops"]
HBM_BW = _BUILTIN["hbm_bw"]
LINK_BW = _BUILTIN["link_bw"]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_ARRAY_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _type_bytes(type_str: str) -> int:
    """Sum bytes over every array in a (possibly tuple) HLO type string."""
    total = 0
    for dtype, dims in _ARRAY_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


HEADER_RE = re.compile(
    r"^(?:ENTRY\s+)?%([\w\.\-]+)\s+\(.*\)\s*->\s*.+\{\s*$"
)
ENTRY_RE = re.compile(r"^ENTRY\s+%([\w\.\-]+)")
TRIP_RE = re.compile(r"known_trip_count[^0-9]*(\d+)")


def match_header(line: str) -> str | None:
    """Computation header: `%name (args...) -> type {` (no ` = `)."""
    if " = " in line.split("->")[0]:
        return None
    m = HEADER_RE.match(line.strip()) or ENTRY_RE.match(line.strip())
    return m.group(1) if m else None


def while_trip(line: str) -> int:
    """Trip count from the while op's backend_config (XLA annotates
    known_trip_count on counted loops — every lax.scan qualifies)."""
    m = TRIP_RE.search(line)
    return int(m.group(1)) if m else 1


@dataclasses.dataclass
class _Computation:
    name: str
    collective_bytes: dict
    collective_counts: dict
    whiles: list  # (trip_count, body_name, cond_name)
    calls: list  # computation names (fusions/calls/conditional branches)


def _parse_computations(hlo: str) -> dict[str, _Computation]:
    comps: dict[str, _Computation] = {}
    cur: _Computation | None = None
    for line in hlo.splitlines():
        stripped = line.strip()
        hname = match_header(stripped)
        if hname is not None:
            cur = _Computation(hname, defaultdict(int), defaultdict(int), [], [])
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        # collectives: `%x = TYPE all-reduce(...)`
        m = re.match(r"(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", stripped)
        if m:
            type_str, op = m.group(1), m.group(2)
            if op in _COLLECTIVES:
                cur.collective_bytes[op] += _type_bytes(type_str)
                cur.collective_counts[op] += 1
            elif op == "while":
                mb = re.search(r"body=%?([\w\.\-]+)", stripped)
                mc = re.search(r"condition=%?([\w\.\-]+)", stripped)
                if mb:
                    cur.whiles.append(
                        (while_trip(stripped), mb.group(1), mc.group(1) if mc else None)
                    )
            elif op == "conditional":
                for name in re.findall(
                    r"(?:branch_computations=\{([^}]*)\}|_computation=%?([\w\.\-]+))",
                    stripped,
                ):
                    for part in name:
                        for n in re.findall(r"%?([\w\.\-]+)", part or ""):
                            cur.calls.append(n)
            elif op in ("fusion", "call", "custom-call", "reduce", "sort",
                        "scatter", "map", "reduce-window", "select-and-scatter"):
                mm = re.findall(r"(?:calls|to_apply)=%?([\w\.\-]+)", stripped)
                cur.calls.extend(mm)
    return comps


def _effective(comps: dict, name: str, memo: dict, stack: frozenset) -> tuple[dict, int]:
    """(bytes-per-op dict, total count) for one computation, recursively."""
    if name in memo:
        return memo[name]
    if name not in comps or name in stack:
        return {}, 0
    c = comps[name]
    out = defaultdict(int, c.collective_bytes)
    cnt = sum(c.collective_counts.values())
    stack = stack | {name}
    for callee in c.calls:
        sub, sc = _effective(comps, callee, memo, stack)
        for k, v in sub.items():
            out[k] += v
        cnt += sc
    for trips, body, cond in c.whiles:
        sub, sc = _effective(comps, body, memo, stack)
        for k, v in sub.items():
            out[k] += v * trips
        cnt += sc * trips
        # the condition itself rarely has collectives, but count it
        subc, scc = _effective(comps, cond, memo, stack) if cond else ({}, 0)
        for k, v in subc.items():
            out[k] += v * trips
        cnt += scc * trips
    memo[name] = (dict(out), cnt)
    return memo[name]


def collective_stats(hlo: str) -> dict:
    comps = _parse_computations(hlo)
    entry = None
    m = re.search(r"ENTRY\s+%?([\w\.\-]+)", hlo)
    if m:
        entry = m.group(1)
    if entry is None or entry not in comps:
        # fall back: whichever computation is named main-ish
        entry = next((n for n in comps if "main" in n), None)
    if entry is None:
        return {"total_bytes": 0, "by_op": {}, "count": 0, "note": "no entry found"}
    memo: dict = {}
    by_op, count = _effective(comps, entry, memo, frozenset())
    return {
        "total_bytes": int(sum(by_op.values())),
        "by_op": {k: int(v) for k, v in sorted(by_op.items())},
        "count": int(count),
    }


# ---------------------------------------------------------------------------
# Roofline terms from a dry-run record
# ---------------------------------------------------------------------------


def roofline_terms(record: dict) -> dict:
    n_dev = record["n_devices"]
    cost = record.get("cost_analysis", {})
    flops = cost.get("flops", 0.0)
    bytes_acc = cost.get("bytes accessed", 0.0)
    coll = record.get("collectives", {}).get("total_bytes", 0)
    # cost_analysis on the partitioned module is per-device program
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_acc / HBM_BW
    collective_s = coll / LINK_BW
    terms = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
    }
    dominant = max(terms, key=lambda k: terms[k])
    bound = max(terms.values())
    total = sum(terms.values())
    return {
        **terms,
        "dominant": dominant,
        "bound_s": bound,
        # roofline fraction: dominant term / sum (overlap-optimistic model)
        "roofline_fraction": bound / total if total else 0.0,
    }
