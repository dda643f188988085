"""A cell small enough for the CPU: the benchmark's harness, family and
generator at toy sizes (Pallas in interpret mode)."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

CONFIG = {
    "name": "tiny", "family": "faust_decoder", "n_layers": 2, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab": 300,
    "rotary_pct": 0.5, "attn_chunk": 16, "dtype": "bfloat16",
    "faust_mlp": {"n_factors": 2, "block": 16, "k": 2},
    "faust_unembed": {"n_factors": 2, "block": 16, "k": 2},
}

CLOSED = {
    "loop": "closed", "clients": 2, "n_slots": 2, "max_len": 64, "pool": 32,
    "prompt": {"dist": "lognormal", "median": 10, "sigma": 0.5, "min": 4, "max": 32},
    "ladder": [16, 32],
    "output": {"dist": "lognormal", "median": 10, "sigma": 0.5, "min": 4, "max": 24},
}

OPEN = dict(CLOSED, loop="open", rate=20.0,
            arrival={"dist": "gamma", "cv": 2.0, "gap_seed": 1})

CHECK = {"min_requests": 2, "min_tokens": 30, "max_requests": 3, "min_compared": 8,
         "limit_logit_gap": 0.5, "limit_logit_gap_mean": 0.05}


class TickClock:
    """A clock that moves ``tick`` seconds each time it is read (and by the
    asked time when slept on): the work a window holds is then the same
    however fast the host runs it."""

    def __init__(self, tick: float = 0.002):
        self.t, self.tick = 0.0, tick

    def __call__(self) -> float:
        self.t += self.tick
        return self.t

    def sleep(self, dt: float) -> None:
        self.t += max(0.0, dt)


def cell(traffic=CLOSED, config=CONFIG, check=CHECK, name="tiny.cell"):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    fam = harness.load_module(
        os.path.join(ROOT, "bench", "families", "faust_decoder.py"), "bench_family_faust_decoder"
    )
    return harness.Cell(name, {"chips": 1}, config, traffic, check, fam,
                        bench["end_to_end"], bench["per_layer"])
