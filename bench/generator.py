"""The one traffic generator: turns a traffic file (``bench/traffic/*.json``)
into requests and their due times.

Every seed gets the same set of sizes and gaps in another order, so the work
in a run does not depend on the seed; the seed chooses the order and the
token contents.

* Lengths are a fixed set of ``pool`` values at evenly spaced quantiles of the
  stated distribution, clipped to its range; a prompt length is rounded up
  to the first rung of the ladder at or above it (each rung is one prefill
  program warmed in set-up).  A traffic file sizes ``pool`` to about the
  requests one window serves, so that every seed serves the same set.  ``ladder`` counts prompt tokens; with a vision
  prefix the rungs count the whole prompt, vision tokens included.
* Open loop: ``round(rate · seconds)`` gaps drawn once from a gamma
  distribution with the stated coefficient of variation (a fixed stream,
  ``gap_seed``), scaled so that they sum to the window, then shuffled by the
  seed: every arrival falls in the window and the rate is exact.
* Closed loop: ``clients`` clients each send the next request of the
  shuffled pool as soon as their previous one has finished.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request as the generator hands it out."""

    index: int
    prompt_len: int  # tokens, vision prefix included
    max_new_tokens: int


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` values at quantiles ``(i + 0.5) / n`` of ``dist``, clipped."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in q])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        vals = dist["min"] + q * (dist["max"] + 1 - dist["min"])
        vals = np.floor(vals)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.round(vals), dist["min"], dist["max"]).astype(np.int64)


def _to_ladder(n: int, ladder: list[int]) -> int:
    for rung in ladder:
        if rung >= n:
            return rung
    raise ValueError(f"length {n} above the ladder's top rung {ladder[-1]}")


class Traffic:
    """Requests and due times of one run of one traffic file."""

    def __init__(self, traffic: dict, config: dict, seed: int, seconds: float):
        self.t = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.vocab = config["vocab"]
        self.nv = config.get("n_vision_tokens", 0) if traffic.get("vision") else 0
        self.d_model = config["d_model"]
        n = traffic["pool"]
        rng = np.random.default_rng([self.seed, 0])
        text = _quantiles(traffic["prompt"], n)
        ladder = sorted(traffic["ladder"])
        prompts = np.array([_to_ladder(int(x) + self.nv, ladder) for x in text])
        outs = _quantiles(traffic["output"], n)
        if int(prompts.max() + outs.max()) > traffic["max_len"]:
            raise ValueError("a prompt and its output overrun max_len")
        self.prompts = rng.permutation(prompts)
        self.outputs = rng.permutation(outs)
        self.loop = traffic["loop"]
        self.due: np.ndarray | None = None
        if self.loop == "open":
            self.due = self._arrivals(rng)

    def _arrivals(self, rng) -> np.ndarray:
        rate = float(self.t["rate"])
        n = max(1, round(rate * self.seconds))
        cv = float(self.t["arrival"]["cv"])
        shape = 1.0 / (cv * cv)
        fixed = np.random.default_rng(self.t["arrival"]["gap_seed"])
        gaps = fixed.gamma(shape, 1.0, size=n)
        gaps = gaps * (self.seconds / gaps.sum())
        gaps = rng.permutation(gaps)
        return np.cumsum(gaps) - gaps  # the first at 0, all inside the window

    def spec(self, i: int) -> Spec:
        j = i % len(self.prompts)
        return Spec(i, int(self.prompts[j]), int(self.outputs[j]))

    def content(self, spec: Spec) -> tuple[np.ndarray, dict]:
        """Token ids (and a vision prefix) of request ``spec.index`` — a pure
        function of the seed and the index, so the reference can rebuild it."""
        rng = np.random.default_rng([self.seed, 1, spec.index])
        tokens = rng.integers(0, self.vocab, size=spec.prompt_len, dtype=np.int32)
        extras = {}
        if self.nv:
            extras["vision_embeds"] = rng.standard_normal(
                (self.nv, self.d_model), dtype=np.float32
            )
        return tokens, extras
