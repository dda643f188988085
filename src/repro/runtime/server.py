"""Batched serving runtime — now a thin shim over the engine.

Request model (legacy surface): a batch of prompts (equal length after
left-padding by the caller), one prefill fills the caches, then
token-by-token greedy decode.  Since PR 7 the actual scheduling lives in
:mod:`repro.runtime.engine` — ``Server.generate`` submits one
:class:`~repro.runtime.engine.Request` per batch row to a fresh
:class:`~repro.runtime.engine.Engine` whose slot pool is exactly the
batch, runs it to completion, and re-stacks the rows.  Uneven-length /
streaming workloads should use the engine directly; this class exists so
existing call sites (and the differential tests, which use it as the
single-request *oracle* against the engine) keep working.

FAµST-parameterized models (``cfg.faust_mlp``/``cfg.faust_unembed``)
route their projections through ``repro.api.FaustOp.apply(backend=
"auto")`` inside the jitted steps; the last backend decision staged
while tracing the serving computations — the decode step's, the
steady-state path — is captured on :class:`ServeStats`
(``faust_dispatch``).  When the FaustSpecs carry a ShardSpec the
decision can be ``fused_sharded`` and the report carries the mesh shape
and per-shard collective bytes; ``ServeStats.mesh_axes`` additionally
records the serving mesh itself.

Accounting (PR 7 bugfix): ``tokens_decoded`` now counts **every**
sampled token — ``b · n_new_tokens`` — including the token sampled from
the prefill logits, which the old ``b · (n_new_tokens − 1)`` loop
excluded from both the count and ``decode_s`` (undercounting
``tokens_per_s`` by one token per stream).  The decode timer starts
after the prefill forward and before the first sample, so every counted
token's sampling time is inside ``decode_s``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ArchConfig
from repro.runtime.engine import Engine, LMExecutor

Array = jax.Array


@dataclasses.dataclass
class ServeStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    tokens_decoded: int = 0
    # last FAµST backend decision staged into the serving computations
    # (None when the model has no FAµST-parameterized projections)
    faust_dispatch: Any = None
    # shard info: the serving mesh's {axis: size} (None off-mesh)
    mesh_axes: dict | None = None
    # supervision outcomes surfaced from EngineStats (ISSUE 10): retried
    # forwards, terminally failed/quarantined streams, degraded-mode
    # dispatch demotions observed during this generate()
    retries: int = 0
    failed: int = 0
    demotions: int = 0

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_decoded / self.decode_s if self.decode_s else 0.0


class Server:
    def __init__(self, cfg: ArchConfig, params, max_len: int, mesh: Mesh | None = None):
        self.cfg, self.params, self.max_len, self.mesh = cfg, params, max_len, mesh
        # dispatch only runs at trace time — remember the decision from the
        # first (cold) generate() so warm-cache calls still report it
        self._faust_dispatch = None
        self._executor: LMExecutor | None = None  # reused across generate()s

    def _sample(self, logits: Array) -> Array:
        """Greedy next-token pick from one step's full logits.

        ``logits`` is ``(B, S, V)`` single-codebook or ``(B, S, K, V)``
        multi-codebook (``models/lm._logits`` stacks codebooks on the
        axis *before* vocab) — the sequence axis is axis 1 in both
        layouts, and both prefill and decode_step emit S == 1.  The last
        position is sliced *here*, once and explicitly.  Returns
        decode_step-shaped tokens: ``(B, K, 1)`` multi-codebook,
        ``(B, 1)`` otherwise.  (The engine's ``LMExecutor.sample`` has
        the same contract; its decode and prefill programs compute this
        expression on the logits they return, so for those ``sample``
        only reads the picks back.  This method remains the documented
        reference and the unit-test surface.)
        """
        step = logits[:, -1]  # (B, V) or (B, K, V)
        tok = jnp.argmax(step, axis=-1).astype(jnp.int32)  # greedy
        if self.cfg.n_codebooks > 1:
            return tok.reshape(tok.shape[0], self.cfg.n_codebooks, 1)
        return tok.reshape(-1, 1)

    def unembed_blockfaust(self):
        """Currently-published unembedding chain (None for dense models)."""
        if self._executor is not None:
            return self._executor.unembed_blockfaust()
        if self.cfg.faust_unembed is None or "faust" not in self.params.get(
            "unembed", {}
        ):
            return None
        from repro.layers.faust_linear import params_to_blockfaust

        return params_to_blockfaust(
            self.params["unembed"]["faust"], self.cfg.faust_unembed,
            self.cfg.d_model, self.cfg.vocab,
        )

    def swap_unembed(self, bf) -> None:
        """Publish a refreshed unembedding chain between ``generate()``
        calls: the cached executor (if one exists) swaps in place — its
        jit caches survive a values-only swap — and ``self.params`` is
        refreshed so future executors are built from the new chain.
        Policy lives in :mod:`repro.streaming.swap` (same contract as
        :meth:`LMExecutor.swap_unembed`)."""
        if self._executor is not None:
            self._executor.swap_unembed(bf)
            self.params = self._executor.params
            return
        if self.cfg.faust_unembed is None or "faust" not in self.params.get(
            "unembed", {}
        ):
            raise ValueError("model has no FAµST unembedding to swap")
        from repro.layers.faust_linear import blockfaust_to_params
        from repro.layers.param import split_annotations

        unembed = dict(self.params["unembed"])
        unembed["faust"], _ = split_annotations(blockfaust_to_params(bf))
        self.params = {**self.params, "unembed": unembed}

    def _executor_for(self, b: int) -> LMExecutor:
        ex = self._executor
        if ex is None or ex.n_slots != b:
            ex = LMExecutor(
                self.cfg, self.params, self.max_len, n_slots=b, mesh=self.mesh
            )
            self._executor = ex
        return ex

    def generate(self, batch: dict, n_new_tokens: int) -> tuple[np.ndarray, ServeStats]:
        b = batch["tokens"].shape[0]
        ex = self._executor_for(b)
        engine = Engine(ex)
        rids = []
        for i in range(b):
            extras = {
                k: np.asarray(v[i]) for k, v in batch.items() if k != "tokens"
            }
            rids.append(
                engine.submit(
                    np.asarray(batch["tokens"][i]), n_new_tokens, extras=extras
                )
            )
        engine.run()
        gen = np.stack([engine.result(r) for r in rids], axis=0)

        es = engine.stats
        stats = ServeStats(
            prefill_s=es.prefill_s,
            decode_s=es.decode_s,
            tokens_decoded=es.tokens_decoded,  # == b * n_new_tokens
            retries=es.retries,
            failed=es.failed,
            demotions=es.demotions,
        )
        if ex.faust_dispatch is not None:
            self._faust_dispatch = ex.faust_dispatch
        stats.faust_dispatch = self._faust_dispatch
        if self.mesh is not None:
            stats.mesh_axes = {str(a): int(s) for a, s in self.mesh.shape.items()}
        return gen, stats
