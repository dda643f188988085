"""Streaming factorization — online PALM4MSA tracking of drifting targets.

The paper factorizes a *fixed* operator once and amortizes the offline
cost over many fast applies.  Every operator in this stack that matters
drifts — trained weights under :mod:`repro.runtime.trainer`, measured
inverse-problem operators — so this subsystem brings the online regime
of Mairal et al., "Online Learning for Matrix Factorization and Sparse
Coding" (arXiv:0908.0050), to PALM4MSA:

* :mod:`repro.streaming.online` — :class:`StreamingFaust`: warm-started
  mini-sweeps against each new target snapshot, a sketched drift monitor,
  and a budget controller choosing skip / incremental sweep / full
  hierarchical refactorization per step.
* :mod:`repro.streaming.swap` — atomic operator hot-swap into the serving
  runtime between decode steps (values-only swaps keep jit caches;
  support changes re-pack).
"""
from repro.streaming.online import StreamingConfig, StreamingFaust, UpdateRecord
from repro.streaming.swap import SwapReport, classify_swap, hot_swap

__all__ = [
    "StreamingConfig",
    "StreamingFaust",
    "UpdateRecord",
    "SwapReport",
    "classify_swap",
    "hot_swap",
]
