"""The benchmark's run: set-up, the measured window, the metrics, and the
comparison with the plain reference that decides ``correct``.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric lives in a file of its own that this module finds by
the names ``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json`` — sizes; its ``family`` names
  ``bench/families/<family>.py`` (weights, arch, plain reference, work counts);
* ``bench/traffic/<traffic>.json`` — the mix, read by ``bench/generator.py``;
* ``bench/cells/<workload>.json`` — what the correctness check samples and
  its limit;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric.

The window drives the program as users run it: ``Engine.step`` →
``LMExecutor.prefill_forward``/``decode_forward`` → ``lm.prefill`` /
``lm.decode_step`` → ``FaustLinear`` through ``FaustOp`` dispatch
(``backend="auto"``) → the Pallas chain kernel.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_CAP_S = 10.0  # a traced run traces the last seconds of its window

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


# ---------------------------------------------------------------------------
# finding the parts by name
# ---------------------------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    entry: dict
    config: dict  # the configuration file
    traffic: dict  # the traffic file
    check: dict  # the cell file
    family: object  # bench/families/<family>.py
    end_to_end: list
    per_layer: list


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def find_cell(workload: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(entries)}")
    entry = entries[workload]
    conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = load_json(os.path.join(root, conf["file"]))
    return Cell(
        name=workload,
        entry=entry,
        config=config,
        traffic=load_json(os.path.join(BENCH, "traffic", entry["traffic"] + ".json")),
        check=load_json(os.path.join(BENCH, "cells", workload + ".json")),
        family=load_module(
            os.path.join(BENCH, "families", config["family"] + ".py"),
            "bench_family_" + config["family"],
        ),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


def metric_reader(name: str):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"), "bench_metric_" + name)


# ---------------------------------------------------------------------------
# compile accounting (JAX's own monitoring events)
# ---------------------------------------------------------------------------


class Meter:
    """Compile seconds and persistent-cache hits and misses."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration
            if event == _COMPILE_EVENTS[-1]:
                self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


def require_tpu(chips: int) -> str | None:
    """Why this process cannot run a cell on ``chips`` chips (None when it
    can).  Dispatch is then priced by the builtin roofline alone, so no host
    state from outside the checkout steers what the program runs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"no TPU (JAX sees {devices[0].platform})"
    if len(devices) < chips:
        return f"the cell needs {chips} chips, JAX sees {len(devices)}"
    os.environ["REPRO_AUTOTUNE"] = "off"
    os.environ["REPRO_ROOFLINE"] = "builtin"
    return None


def use_compile_cache() -> str:
    """JAX's persistent cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), with
    every program cached, however quick its compile or small its entry."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ---------------------------------------------------------------------------
# the executor seen from the benchmark: spans and a record of the calls
# ---------------------------------------------------------------------------


class Recorder:
    """Wraps the program's ``LMExecutor`` for the engine: each call into the
    model step gets a host span, and while ``recording`` the calls are kept
    — ``("prefill", n)`` or ``("decode", [tokens cached per live row])`` —
    so the work each one required can be counted afterwards."""

    def __init__(self, executor):
        self._ex = executor
        self.n_slots = executor.n_slots
        self._pos: dict[int, int] = {}
        self.recording = False
        self.calls: list = []

    def __getattr__(self, name):
        return getattr(self._ex, name)

    def prefill_forward(self, slot, prompt, extras):
        import jax

        n = int(np.asarray(prompt).shape[-1])
        with jax.profiler.TraceAnnotation("bench.prefill_forward"):
            out = self._ex.prefill_forward(slot, prompt, extras)
        self._pos[slot] = n
        if self.recording:
            self.calls.append(("prefill", n))
        return out

    def decode_forward(self, slots, tokens):
        import jax

        context = [self._pos.get(s, 0) for s in slots]
        with jax.profiler.TraceAnnotation("bench.decode_forward"):
            out = self._ex.decode_forward(slots, tokens)
        for s in slots:
            self._pos[s] = self._pos.get(s, 0) + 1
        if self.recording:
            self.calls.append(("decode", context))
        return out

    def sample(self, logits):
        import jax

        with jax.profiler.TraceAnnotation("bench.sample"):
            return self._ex.sample(logits)

    def row_finite(self, logits):
        import jax

        with jax.profiler.TraceAnnotation("bench.row_finite"):
            return self._ex.row_finite(logits)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class System:
    params: dict
    executor: object
    recorder: Recorder
    dispatch: dict  # (role, rows) -> DispatchReport


def build(cell: Cell, seed: int) -> System:
    """Weights from the seed, the program's executor over them, and every
    program the cell's traffic will run compiled (or read from the cache)."""
    from repro.runtime.engine import LMExecutor

    c, t = cell.config, cell.traffic
    fam = cell.family
    t0 = time.perf_counter()
    params = fam.make_params(c, seed)
    t1 = time.perf_counter()
    arch = fam.arch(c)
    ex = LMExecutor(arch, params, max_len=t["max_len"], n_slots=t["n_slots"])
    rec = Recorder(ex)
    warm_up(rec, cell)
    t2 = time.perf_counter()
    log(f"bench: weights {t1 - t0:.3f}s, executor and warm-up {t2 - t1:.3f}s")
    return System(params, ex, rec, dispatch_reports(cell, arch, params))


def warm_up(rec: Recorder, cell: Cell) -> None:
    """Every prefill rung and every live batch size 1..n_slots, each with
    its sampling and finiteness programs."""
    c, t = cell.config, cell.traffic
    nv = c.get("n_vision_tokens", 0) if t.get("vision") else 0
    extras = {}
    if nv:
        extras["vision_embeds"] = np.zeros((nv, c["d_model"]), np.float32)
    for n in sorted(t["ladder"]):
        logits = rec.prefill_forward(0, np.zeros((n,), np.int32), extras)
        rec.sample(logits)
        rec.row_finite(logits)
    for b in range(1, t["n_slots"] + 1):
        slots = list(range(b))
        for s in slots:
            rec._pos.setdefault(s, 1)
        logits = rec.decode_forward(slots, np.zeros((b, 1), np.int32))
        rec.sample(logits)
        rec.row_finite(logits)
        rec.dispatch_for(b)
    rec._pos.clear()


def dispatch_reports(cell: Cell, arch, params) -> dict:
    """What ``backend="auto"`` decides for each chain at each row count the
    traffic makes it run (the program's own dispatch, queried, not forced)."""
    import jax
    import jax.numpy as jnp

    from repro.api import FaustOp
    from repro.api import dispatch as D
    from repro.layers.faust_linear import params_to_blockfaust

    chains = cell.family.chains(cell.config)
    if not chains:
        return {}
    t = cell.traffic
    dtype = jnp.bfloat16 if cell.config["dtype"] == "bfloat16" else jnp.float32
    rows = set(range(1, t["n_slots"] + 1)) | set(t["ladder"])
    spec = {"gate": (arch.faust_mlp, "w_gate"), "up": (arch.faust_mlp, "w_up"),
            "down": (arch.faust_mlp, "w_down"), "unembed": (arch.faust_unembed, None)}
    out = {}
    for role, ch in chains.items():
        fs, key = spec[role]
        if key is None:
            p = params["unembed"]["faust"]
        else:
            p = jax.tree_util.tree_map(lambda a: a[0], params["stages"][0][0]["mlp"][key])
        op = FaustOp.from_blockfaust(params_to_blockfaust(p, fs, ch.in_dim, ch.out_dim))
        for r in sorted(rows):
            out[(role, r)] = D.dispatch(op, r, dtype, record=False)
    return out


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Rec:
    """One request as the benchmark saw it."""

    spec: object
    rid: str
    due: float  # when it was due (open loop) or sent (closed loop)
    sent: float
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)
    first_t: float | None = None
    state: str = "queued"


class Driver:
    """Offers a cell's traffic to the engine and keeps each request's token
    times.  One thread: requests are released between engine steps."""

    def __init__(self, engine, traffic, clock=time.perf_counter, sleep=time.sleep):
        self.engine = engine
        self.traffic = traffic
        self.clock = clock
        self.sleep = sleep
        self.recs: dict[str, Rec] = {}
        self.live: dict[str, Rec] = {}
        self.next_index = 0
        self.next_due = 0  # open loop: index of the next due time to release
        self.lateness: list[float] = []
        self.steps: list[tuple[float, float]] = []

    def submit(self, due: float) -> Rec:
        spec = self.traffic.spec(self.next_index)
        self.next_index += 1
        tokens, extras = self.traffic.content(spec)
        now = self.clock()
        rid = self.engine.submit(tokens, spec.max_new_tokens, extras=extras,
                                 rid=f"q{spec.index}")
        rec = Rec(spec, rid, due=due, sent=now)
        self.recs[rid] = rec
        self.live[rid] = rec
        return rec

    def step(self) -> list[Rec]:
        """One engine step; returns the requests that ended in it."""
        import jax

        t_a = self.clock()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.engine.step()
        t_b = self.clock()
        self.steps.append((t_a, t_b))
        ended = []
        for rid, rec in list(self.live.items()):
            req = self.engine.running.get(rid) or self.engine.done.get(rid)
            if req is None:
                continue
            new = req.generated[len(rec.tokens):]
            if new:
                if not rec.tokens:
                    rec.first_t = req.first_token_t
                    rec.times.append(req.first_token_t)
                    rec.times.extend([t_b] * (len(new) - 1))
                else:
                    rec.times.extend([t_b] * len(new))
                rec.tokens.extend(int(np.asarray(x).reshape(-1)[0]) for x in new)
            if rid in self.engine.done:
                rec.state = req.state
                del self.live[rid]
                ended.append(rec)
        return ended

    def ramp(self, clients: int) -> None:
        """Closed loop: every client's first request admitted before the
        window, so the window opens on a full batch."""
        for _ in range(clients):
            self.submit(self.clock())
        while self.engine.queue:
            self.step()

    def run(self, t0: float, seconds: float, stop: float | None = None) -> None:
        """Offer the traffic of the window that opened at ``t0`` until it
        closes, or until ``stop``; a later call carries on where this one
        stopped."""
        import jax

        t_end = t0 + seconds
        t_stop = t_end if stop is None else min(stop, t_end)
        due = self.traffic.due
        while True:
            now = self.clock()
            if now >= t_stop:
                break
            if due is not None:
                with jax.profiler.TraceAnnotation("bench.generator"):
                    while self.next_due < len(due) and t0 + due[self.next_due] <= now:
                        self.submit(t0 + due[self.next_due])
                        self.lateness.append(self.clock() - (t0 + due[self.next_due]))
                        self.next_due += 1
            if self.engine.n_pending:
                ended = self.step()
                if due is None:  # closed loop: each ended request's client sends again
                    with jax.profiler.TraceAnnotation("bench.generator"):
                        for _ in ended:
                            t = self.clock()
                            self.submit(t)
            else:
                nxt = t0 + due[self.next_due] if due is not None and self.next_due < len(due) else t_end
                with jax.profiler.TraceAnnotation("bench.wait"):
                    self.sleep(max(0.0, min(nxt, t_stop) - self.clock()))


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------


def p95(values) -> float | None:
    return float(np.percentile(np.asarray(values, np.float64), 95)) if len(values) else None


def end_to_end(recs, t0: float, seconds: float) -> dict:
    """Tokens per second over the window, and the 95th percentiles of time to
    first token and of the gaps between tokens, all from token times."""
    t1 = t0 + seconds
    n_tok = 0
    ttft, itl = [], []
    for r in recs:
        ts = np.asarray(r.times, np.float64)
        inside = (ts >= t0) & (ts < t1)
        n_tok += int(inside.sum())
        if r.first_t is not None and t0 <= r.first_t < t1:
            ttft.append((r.first_t - r.due) * 1e3)
        if len(ts) > 1:
            both = inside[1:] & inside[:-1]
            itl.extend(((ts[1:] - ts[:-1])[both] * 1e3).tolist())
    return {
        "tokens_per_s": n_tok / seconds,
        "ttft_p95_ms": p95(ttft),
        "itl_p95_ms": p95(itl),
        "n_ttft": len(ttft),
        "n_itl": len(itl),
        "tokens": n_tok,
    }


# ---------------------------------------------------------------------------
# correctness: the served tokens against the plain reference
# ---------------------------------------------------------------------------


def _padded(n: int) -> int:
    return 512 if n <= 512 else -(-n // 512) * 512


def sample_for_check(recs, check: dict, seed: int) -> list:
    """Requests with served tokens (finished or still in flight when the
    window closed; failed ones are counted apart): the longest, then others
    drawn from the seed, at least ``min_requests`` of them and more until
    ``min_tokens`` served tokens are in, at most ``max_requests``.  Each
    request is compared over all its served tokens, so the sample spans
    several of the engine's slots and the longest context reached."""
    served = sorted((r for r in recs if r.tokens and r.state in ("done", "queued", "running")),
                    key=lambda r: (-len(r.tokens), r.rid))
    if not served:
        return []
    rng = np.random.default_rng([seed % 2**64, 2])
    rest = [served[i] for i in rng.permutation(np.arange(1, len(served)))]
    out, n_tok = [], 0
    for r in [served[0]] + rest:
        if len(out) >= check["max_requests"]:
            break
        if len(out) >= check["min_requests"] and n_tok >= check["min_tokens"]:
            break
        out.append(r)
        n_tok += len(r.tokens)
    return out


def served_gaps(family, config, params, traffic, rec, *, control: bool = False) -> np.ndarray:
    """Per served token of ``rec``: how far the reference's logit of the
    token lies below the reference's best (``control``: of the token the
    int8 control puts first at that position instead)."""
    prompt, extras = traffic.content(rec.spec)
    seq = np.concatenate([prompt, np.asarray(rec.tokens, np.int32)])
    n, p = len(seq), len(prompt)
    tokens = np.zeros(_padded(n), np.int32)
    tokens[:n] = seq
    targets = np.zeros_like(tokens)
    targets[: n - 1] = seq[1:]
    vision = extras.get("vision_embeds")
    if control:
        _, top = family.reference_pass(params, config, tokens, vision, targets, quant=True)
        targets = top
    gaps, _ = family.reference_pass(params, config, tokens, vision, targets)
    return gaps[p - 1 : n - 1]


def gap_readings(cell: Cell, params, traffic, picked, *, control: bool = False) -> dict:
    """The widest and the mean gap over every served token of ``picked``."""
    gaps = [served_gaps(cell.family, cell.config, params, traffic, r, control=control)
            for r in picked]
    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"logit_gap": float(allg.max()) if allg.size else 0.0,
            "logit_gap_mean": float(allg.mean()) if allg.size else 0.0,
            "tokens": int(allg.size)}


def check_correct(cell: Cell, params, traffic, recs, seed: int, failed: int, *,
                  control: bool = False) -> tuple[bool, dict]:
    """Whether the served tokens pass the cell's limits, and each number
    compared beside its limit.  ``control`` puts the int8 control's tokens
    in the program's place at the same positions: it has to fail."""
    picked = sample_for_check(recs, cell.check, seed)
    got = gap_readings(cell, params, traffic, picked, control=control)
    lim = cell.check
    checks = {
        "logit_gap": {"value": got["logit_gap"], "limit": lim["limit_logit_gap"]},
        "logit_gap_mean": {"value": got["logit_gap_mean"], "limit": lim["limit_logit_gap_mean"]},
        "failed_requests": {"value": failed, "limit": 0},
        "requests_compared": {"value": len(picked), "limit": lim["min_requests"]},
        "tokens_compared": {"value": got["tokens"], "limit": lim["min_compared"]},
    }
    ok = (
        got["logit_gap"] <= lim["limit_logit_gap"]
        and got["logit_gap_mean"] <= lim["limit_logit_gap_mean"]
        and failed == 0
        and len(picked) >= lim["min_requests"]
        and got["tokens"] >= lim["min_compared"]
    )
    return ok, checks


# ---------------------------------------------------------------------------
# the traced window: per-layer metrics
# ---------------------------------------------------------------------------


def stats_view(stats) -> dict:
    return {"steps": stats.steps, "decode_s": stats.decode_s,
            "prefill_s": stats.prefill_s, "admitted": stats.admitted,
            "occupancy": dict(stats.occupancy)}


def stats_delta(a: dict, b: dict) -> dict:
    occ = {k: b["occupancy"].get(k, 0) - a["occupancy"].get(k, 0) for k in b["occupancy"]}
    return {"steps": b["steps"] - a["steps"], "decode_s": b["decode_s"] - a["decode_s"],
            "prefill_s": b["prefill_s"] - a["prefill_s"],
            "admitted": b["admitted"] - a["admitted"],
            "occupancy": {k: v for k, v in occ.items() if v}}


def bounds(ctx) -> dict:
    """How many traced prefills and decode steps each roofline bound sets."""
    from bench.peaks import least_time_s

    out: dict = {}
    for kind, arg in ctx.calls:
        work = ctx.family.decode_work if kind == "decode" else ctx.family.prefill_work
        _, bound = least_time_s(*work(ctx.config, arg), ctx.peaks, ctx.config["dtype"])
        out[f"{kind}:{bound}"] = out.get(f"{kind}:{bound}", 0) + 1
    return out


def per_layer(cell: Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def device_record(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        started: float, cell: Cell | None = None) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax

    from repro.runtime.engine import Engine

    cell = cell or find_cell(workload)
    cache = use_compile_cache()
    meter = Meter()
    log(f"bench: workload={workload} seed={seed} seconds={seconds} trace={int(trace)}")
    log(f"bench: device {jax.devices()[0].device_kind} x{len(jax.devices())}; compile cache {cache}")

    from bench.generator import Traffic

    traffic = Traffic(cell.traffic, cell.config, seed, seconds)
    system = build(cell, seed)
    for (role, rows), rep in sorted(system.dispatch.items()):
        log(f"bench: dispatch {role} rows={rows}: {rep.backend} bt={rep.bt} ({rep.source})")
    engine = Engine(system.recorder, clock=time.perf_counter)
    driver = Driver(engine, traffic)
    if cell.traffic["loop"] == "closed":
        t_r = time.perf_counter()
        driver.ramp(cell.traffic["clients"])
        log(f"bench: ramp {time.perf_counter() - t_r:.3f}s")
    setup_meter = meter.snapshot()
    t0 = time.perf_counter()
    setup_s = time.time() - started
    if trace:
        # the whole window runs; the trace, the recorded calls and the
        # engine's counters cover its last TRACE_CAP_S seconds, which hold
        # their share of admissions as well as decode steps
        driver.run(t0, seconds, stop=t0 + max(0.0, seconds - TRACE_CAP_S))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans come from TraceAnnotation
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    t_measured = time.perf_counter()
    s0 = stats_view(engine.stats)
    system.recorder.recording = True
    with jax.profiler.TraceAnnotation("bench.window"):
        driver.run(t0, seconds)
    t_close = time.perf_counter()
    system.recorder.recording = False
    s1 = stats_view(engine.stats)
    if trace:
        jax.profiler.stop_trace()
    window_meter = meter.snapshot()
    compiles_in_window = window_meter["compiles"] - setup_meter["compiles"]
    log(f"bench: set-up {setup_s:.3f}s; compile {setup_meter['compile_s']:.3f}s over "
        f"{setup_meter['compiles']} programs; cache hits {setup_meter['cache_hits']} "
        f"misses {setup_meter['cache_misses']}; compiles in window {compiles_in_window}")
    recs = list(driver.recs.values())
    failed = sum(r.state not in ("done", "queued", "running") for r in recs)
    attempted = len(recs)
    late = driver.lateness
    log(f"bench: requests sent {attempted} done {sum(r.state == 'done' for r in recs)} "
        f"failed {failed}; generator late p50 "
        f"{(np.median(late) * 1e3 if late else 0.0):.3f}ms max "
        f"{(max(late) * 1e3 if late else 0.0):.3f}ms; window overran by "
        f"{(t_close - t0 - seconds):.3f}s")
    device = device_record(jax)

    result_metrics = {}
    breakdown = None
    if trace:
        from bench import trace_reduce

        tr = trace_reduce.reduce_dir(TRACE_DIR)
        calls = list(system.recorder.calls)
        steps = [(a, b) for a, b in driver.steps if a >= t_measured]
        ctx = SimpleNamespace(
            trace=tr, stats=stats_delta(s0, s1), calls=calls,
            step_wall_s=sum(b - a for a, b in steps),
            config=cell.config, family=cell.family, dispatch=system.dispatch,
            peaks=_peaks(device["kind"]),
        )
        result_metrics = per_layer(cell, ctx)
        log(f"bench: roofline bounds of the traced steps: {bounds(ctx)}")
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        breakdown = tr.breakdown()
        log(f"bench: trace {tr.summary()} (the window's last {t_close - t_measured:.3f}s)")
    else:
        e2e = end_to_end(recs, t0, seconds)
        log(f"bench: window {seconds}s tokens {e2e['tokens']} ttft samples "
            f"{e2e['n_ttft']} itl samples {e2e['n_itl']}")
        values = {"setup_s": setup_s, **e2e}
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is not None:
                result_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # correctness, after the peak is read and the program's state is freed
    system.executor.pool = None
    engine = None
    t_ref = time.perf_counter()
    correct, checks = check_correct(cell, system.params, traffic, recs, seed, failed)
    log(f"bench: reference check {time.perf_counter() - t_ref:.3f}s")
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": result_metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def _peaks(kind: str) -> dict:
    from bench.peaks import peaks_for

    return peaks_for(kind)
