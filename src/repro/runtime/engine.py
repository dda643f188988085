"""Continuous-batching FAµST serving engine.

The paper's premise is that multi-layer sparse factorizations make
*applying* an operator cheap — and serving is where apply cost dominates:
many concurrent streams of uneven length, decoded one token at a time.
``runtime/server.py``'s single-batch prefill/decode loop forces every
stream in a batch to share one admission time and one token budget; this
module replaces it with a proper engine:

* :class:`Request` — one stream: its own prompt length, token budget and
  arrival time.
* :class:`SlotAllocator` — a fixed pool of KV-cache *slots* (rows of one
  ``lm.make_caches(cfg, n_slots, max_len)`` pytree).  Deterministic
  lowest-free-slot assignment on admit, returned on finish — the
  allocation schedule is a pure function of the arrival/finish sequence,
  which the simulation tests rely on.
* :class:`Engine` — the scheduler.  Each :meth:`Engine.step` admits
  queued requests while slots are free (per-request prefill written into
  the slot's pool row), then runs **one** decode step over the live
  batch.  Requests that hit their budget complete and free their slot
  immediately — the batch *breathes*, which is exactly the small-batch
  regime where the fused chain kernel wins (BENCH ``apply_*`` rows).
* :class:`EngineStats` — queue depth and batch-occupancy over the
  decode steps, admitted/completed/evicted counts, per-request TTFT/TPOT,
  and the FAµST dispatch decision at each live batch size.  Nothing in
  it grows with the number of steps served.

**Static shapes.** ``lm.prefill`` / ``lm.decode_delta`` never see a
dynamic shape: the cache pool keeps the slot dim at ``n_slots``, and a
decode step runs every pool row where it lies (``lm.decode_delta`` reads
the pool, free slots fed a dummy token) and writes back only what the
token changed, for the live rows only (``lm.scatter_cache_slots``: one
new K/V entry per attention layer and row, the small Mamba states
whole).  A free slot's cache and ``pos`` stay as they were.  Per-slot
position tracking (``KVCache.pos``/``MambaCache.pos`` are per row)
replaces left-padding: a reused slot simply restarts its row's
positions, and stale entries beyond the new occupant's ``pos`` are
masked by the ring-attention window math.  jit compiles one decode
program per pool and one prefill per prompt length, not per live batch
size, slot or schedule.

**Live-batch dispatch.** Each decode step consults the dispatch layer at
the *live* batch size (:meth:`repro.api.FaustOp.dispatch_for`,
``record=False``) so the backend choice — and the fitted ``bt`` tile —
follows the batch as it breathes; the
:class:`~repro.api.dispatch.DispatchReport` of each live batch size is
recorded on :class:`EngineStats`.
:class:`LMExecutor` answers the query once per batch size and keeps the
answer until the next :meth:`LMExecutor.swap_unembed`.

**Eviction.** ``Engine.evict(rid)`` preempts a live request: its slot is
freed (and may be reused immediately), the request returns to the *front*
of the queue, and re-admission prefills ``prompt + generated`` — greedy
decode recomputes the same stream token-exactly, so preemption is
invisible in the output (pinned by tests/test_engine_sim.py).  Eviction
is starvation-proof: re-queued preemptees are age-ordered (oldest
arrival first) and a request that has been evicted ``max_evictions``
times is pinned to its slot (``evict`` returns False).

**Supervision.** One NaN logit, one failing kernel launch, or one stuck
request must not take the engine down (ISSUE 10):

* *Retry with backoff* — a forward that raises preempts the affected
  requests through the eviction path (re-prefill of ``prompt +
  generated`` keeps retried streams token-exact), charges each a retry
  against ``retry_budget`` and delays re-admission by an exponential
  backoff; over-budget requests turn terminal ``FAILED``.
* *NaN quarantine* — non-finite logits rows (divergence — e.g. a
  regressed FAµST unembedding) fail exactly the affected stream, never
  the batch.
* *Deadlines* — ``submit(..., ttl=...)`` sets a wall deadline; expiry
  frees the slot (or sheds the queued request) with terminal state
  ``TIMED_OUT``.
* *Admission control* — ``max_queue`` sheds submissions at the door
  (terminal ``REJECTED``) instead of queueing unboundedly.

Terminal states and counters live on :class:`Request` /
:class:`EngineStats`; every fault path is proven by scripted
deterministic traces in ``tests/test_engine_faults.py`` driving
:class:`repro.runtime.faults.FaultInjector` — including that a
zero-fault injector run is byte-identical to no injector at all, and a
zero-fault engine is byte-identical to the pre-supervision scheduler
(the fast paths add no clock reads).

The model side lives behind the small :class:`Executor` interface so the
scheduler itself is testable with a pure-numpy deterministic model
(``tests/engine_sim.py``) — zero jax, zero wall-clock.
:class:`LMExecutor` is the real jax implementation;
``runtime/server.py``'s ``Server.generate`` is now a thin shim over
``Engine`` + ``LMExecutor``.

**Tracing.** The engine and :class:`LMExecutor` mark their work with host
spans (``jax.profiler.TraceAnnotation``), which land in the profiler's
trace on the same clock as the device's op events, so every stretch in
which the chip sits idle can be put down to what the host was doing.
To record them, wrap serving in ``with jax.profiler.trace(log_dir):``
and open the ``.xplane.pb`` it writes (TensorBoard, Perfetto, or
``jax.profiler.ProfileData``).  When no trace is recording a span costs
one check and builds nothing.  The spans, outermost first:

* ``engine.step`` — one :meth:`Engine.step` tick (arg ``step``, a tick
  counter); the device idles *between* these spans while the caller's
  own code runs.
* ``engine.admit`` — one admission: prefill, its sample and NaN guard
  (args ``rid``, ``tokens``).
* ``engine.decode`` — the decode half of the tick (arg ``rows``), with
  ``engine.dispatch_query`` around the advisory dispatch lookup.
* ``executor.prefill`` / ``executor.decode`` — the forward call
  (``executor.decode`` with args ``rows``, the live rows, and
  ``pool_rows``, the rows the decode program computes), split
  into ``executor.launch`` (argument conversion, host-to-device copies and
  the jitted calls until they return, the ragged-tail replay included)
  and ``executor.wait`` (blocking on the logits).
* ``executor.sample`` (arg ``fused``) — the step's one readback: the
  decode and prefill programs pick each row's greedy tokens and its
  all-finite flag themselves, and ``sample`` copies those few int32s to
  the host (``fused`` 1).  Logits that are not what the last step
  returned (the ``FaultInjector``'s poisoned copy, a slice) are reduced
  eagerly instead, with a readback of their own (``fused`` 0).
* ``executor.row_finite`` (arg ``fused``) — the NaN guard, read from the
  same host copy with no further sync (or reduced eagerly, as above).

Time inside ``engine.step`` but outside every ``executor.*`` span is the
engine's own bookkeeping (scheduling, the dispatch query, token append,
completion).
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import os
import sys
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Protocol, Sequence

import numpy as np

__all__ = [
    "Request",
    "SlotAllocator",
    "EngineStats",
    "Executor",
    "LMExecutor",
    "Engine",
]


_NO_SPAN = contextlib.nullcontext()


def _span(name: str, *args):
    """Host span ``name`` on the profiler's clock (see "Tracing" above);
    ``args`` alternate argument names and cheap scalar values.  While no
    trace is recording — always, in a process that never loaded jax's
    profiler — it returns a shared no-op context and builds nothing."""
    prof = sys.modules.get("jax.profiler")
    if prof is None or not prof.TraceAnnotation.is_enabled():
        return _NO_SPAN
    return prof.TraceAnnotation(name, **dict(zip(args[::2], args[1::2])))


# ---------------------------------------------------------------------------
# Requests
# ---------------------------------------------------------------------------


QUEUED, RUNNING, DONE = "queued", "running", "done"
# terminal non-success states (supervision; see module docstring)
REJECTED, TIMED_OUT, FAILED = "rejected", "timed_out", "failed"


@dataclasses.dataclass
class Request:
    """One generation stream.

    ``prompt`` is a single row — ``(S,)`` int32, or ``(K, S)`` for
    multi-codebook archs.  ``extras`` carries per-request side inputs
    (e.g. a ``vision_embeds`` row for VLM archs), batched up by the
    executor.  Runtime fields are engine-owned.
    """

    rid: str
    prompt: np.ndarray
    max_new_tokens: int
    extras: dict = dataclasses.field(default_factory=dict)
    arrival: float = 0.0
    # --- engine-owned runtime state ---
    state: str = QUEUED
    slot: int | None = None
    generated: list = dataclasses.field(default_factory=list)
    last_token: np.ndarray | None = None
    first_token_t: float | None = None
    done_t: float | None = None
    n_evictions: int = 0
    n_retries: int = 0
    deadline: float | None = None  # absolute clock time (arrival + ttl)
    not_before: float = 0.0  # retry backoff: earliest re-admission time
    error: str | None = None  # why state is REJECTED/TIMED_OUT/FAILED

    def prompt_full(self) -> np.ndarray:
        """Prompt plus everything generated so far — what a re-admission
        prefills, so greedy decode resumes the stream token-exactly."""
        if not self.generated:
            return self.prompt
        gen = np.concatenate(self.generated, axis=-1).astype(self.prompt.dtype)
        return np.concatenate([self.prompt, gen], axis=-1)

    def output(self) -> np.ndarray:
        """Generated tokens: ``(n,)`` or ``(K, n)`` multi-codebook."""
        if not self.generated:
            k = self.prompt.shape[0] if self.prompt.ndim == 2 else None
            return np.zeros((k, 0) if k else (0,), np.int32)
        return np.concatenate(self.generated, axis=-1)


# ---------------------------------------------------------------------------
# Slot allocator
# ---------------------------------------------------------------------------


class SlotAllocator:
    """Fixed pool of cache slots with deterministic assignment.

    ``alloc`` always hands out the lowest free slot index (a min-heap),
    so the slot schedule is a pure function of the admission/finish
    sequence — the property the simulation tests pin.  Double-alloc and
    double-free are hard errors, not corruptions.
    """

    def __init__(self, n_slots: int):
        if n_slots <= 0:
            raise ValueError(f"n_slots must be positive; got {n_slots}")
        self.n_slots = n_slots
        self._free: list[int] = list(range(n_slots))  # already a valid heap
        self._owner: dict[int, str] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    def owner_of(self, slot: int) -> str | None:
        return self._owner.get(slot)

    def alloc(self, rid: str) -> int:
        if not self._free:
            raise RuntimeError("slot pool exhausted")
        slot = heapq.heappop(self._free)
        assert slot not in self._owner, f"slot {slot} double-assigned"
        self._owner[slot] = rid
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._owner:
            raise ValueError(f"slot {slot} is not allocated (double free?)")
        del self._owner[slot]
        heapq.heappush(self._free, slot)


# ---------------------------------------------------------------------------
# Stats
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EngineStats:
    """Scheduler-level accounting.

    ``tokens_decoded`` counts **every** sampled token, including the one
    sampled from the prefill logits — the accounting fix over the old
    ``ServeStats`` (which counted ``b·(n_new−1)``, excluding the
    prefill-sampled token from both the count and ``decode_s``).  The
    decode timer here starts after the prefill forward and *before* the
    first sample, so ``tokens_per_s = tokens_decoded / decode_s`` is
    consistent: every counted token's sampling time is inside
    ``decode_s``.
    """

    prefill_s: float = 0.0
    decode_s: float = 0.0
    tokens_decoded: int = 0
    steps: int = 0  # decode steps executed (batch of any size counts 1)
    admitted: int = 0  # prefills run (re-admissions count again)
    completed: int = 0
    evicted: int = 0
    swaps: int = 0  # operator hot-swaps published (streaming.swap)
    # supervision counters (terminal states + recovery actions)
    rejected: int = 0  # shed at submit (queue over max_queue)
    timed_out: int = 0  # deadline/TTL expiry (running or queued)
    failed: int = 0  # retry budget exhausted or quarantined
    retries: int = 0  # re-queues after a raised forward
    quarantined: int = 0  # streams killed by the non-finite-logits guard
    demotions: int = 0  # degraded-mode dispatch fallbacks observed
    swap_rejects: int = 0  # guarded hot-swaps rolled back (streaming.swap)
    # decode-step observability, constant in size however long it serves
    queue_depth_max: int = 0  # deepest queue seen at a decode step
    queue_depth_sum: int = 0  # queue depth summed over decode steps
    decode_masked_rows: int = 0  # rows decoded for free slots, over decode steps
    occupancy: dict = dataclasses.field(default_factory=dict)  # B_live -> steps
    dispatch_by_batch: dict = dataclasses.field(default_factory=dict)  # B_live -> last report
    # per-request latency (seconds, under the engine's clock)
    ttft_s: dict = dataclasses.field(default_factory=dict)
    tpot_s: dict = dataclasses.field(default_factory=dict)
    # parity with the old ServeStats surface
    faust_dispatch: Any = None  # last decision *staged* into a computation
    mesh_axes: dict | None = None

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_decoded / self.decode_s if self.decode_s else 0.0

    def backend_counts(self) -> dict:
        """Histogram of dispatch decisions over the decode steps:
        backend -> steps (a live batch size's steps count under the
        backend last reported for it)."""
        counts: dict[str, int] = {}
        for b, rep in self.dispatch_by_batch.items():
            if rep is not None:
                counts[rep.backend] = counts.get(rep.backend, 0) + self.occupancy.get(b, 0)
        return counts


# ---------------------------------------------------------------------------
# Executor interface + the real jax implementation
# ---------------------------------------------------------------------------


class Executor(Protocol):
    """What the scheduler needs from a model.

    The engine times ``prefill_forward`` into ``prefill_s`` and
    ``decode_forward`` + ``sample`` into ``decode_s``; implementations
    should block on device results inside these calls so the timings are
    honest.  ``tests/engine_sim.py`` provides a pure-numpy deterministic
    implementation with slot-hygiene assertions.
    """

    n_slots: int
    # Optional ``pool_rows``: the rows every decode step computes whatever
    # the live batch (LMExecutor: the whole pool); absent, the live rows.

    def prefill_forward(self, slot: int, prompt: np.ndarray, extras: dict):
        """Run the prompt through the model into cache slot ``slot``;
        return the last position's logits ``(1, 1, V)`` / ``(1, 1, K, V)``."""
        ...

    def decode_forward(self, slots: Sequence[int], tokens: np.ndarray):
        """One decode step for the live rows ``slots`` feeding ``tokens``
        ``(B, 1)`` / ``(B, K, 1)``; returns logits ``(B, 1, V[, K…])``."""
        ...

    def sample(self, logits) -> np.ndarray:
        """Greedy tokens from one step's logits: ``(B, 1)`` / ``(B, K, 1)``."""
        ...

    def free(self, slot: int) -> None:
        """Slot released — hygiene hook (the sim poisons the row)."""
        ...

    def dispatch_for(self, batch: int):
        """Advisory FAµST dispatch report at live batch ``batch`` (None
        when the model has no FAµST projections)."""
        ...


class LMExecutor:
    """The real model behind the engine: a slot-paged cache pool plus
    jitted prefill/decode closures over ``models/lm``.

    * ``_prefill_fn(params, batch, pool, slot)`` prefills a fresh
      single-row cache and writes it into pool row ``slot`` with a
      ``dynamic_update_slice`` along the slot axis — ``slot`` is traced,
      so admissions into different slots share one compilation (one per
      distinct prompt length).
    * ``_decode_fn(params, tokens, pool, slot_idx)`` decodes all
      ``n_slots`` rows of the pool where they lie (``tokens`` in pool-row
      order, a dummy token for free slots) and writes the new entries of
      the rows ``slot_idx`` lists — the live slots, padded with
      ``n_slots`` — returning the logits in ``slot_idx`` order: one
      compilation per pool.

    Both return ``(logits, picks, pool)``: ``picks`` holds each row's
    greedy token per codebook and its all-finite flag, ``(B, K + 1)``
    int32, taken inside the program from the logits it returns.
    :meth:`sample` and :meth:`row_finite` answer for the last step's
    logits from one host copy of them; ``picks_fused`` counts those
    calls and ``picks_eager`` the calls on any other logits.

    Both donate the pool, so the slot pool is updated in place
    buffer-wise.  The FAµST dispatch staged while tracing is captured
    (same mark technique as the old ``Server``) on ``faust_dispatch``;
    :meth:`dispatch_for` answers the engine's per-step advisory query
    from the unembedding chain — the projection every decode step pays —
    pricing each live batch size once.
    """

    def __init__(self, cfg, params, max_len: int, n_slots: int, mesh=None):
        import jax
        import jax.numpy as jnp

        from repro.distributed import sharding as shd
        from repro.models import lm

        self.cfg, self.params, self.mesh = cfg, params, mesh
        self.max_len, self.n_slots = max_len, n_slots
        self._jnp, self._lm = jnp, lm
        self._act_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self.pool = lm.make_caches(cfg, n_slots, max_len, dtype=self._act_dtype)
        self.pool_rows = n_slots  # rows every decode step computes
        self.faust_dispatch = None  # last decision staged into a trace
        self._faust_op = self._build_faust_op()
        self._dispatch_memo: dict = {}  # live batch -> advisory report

        # the last step's logits object, its program's picks, and their
        # host copy once made: (logits, picks, host or None)
        self._step_picks = None
        self.picks_fused = 0  # sample/row_finite calls read from the program's picks
        self.picks_eager = 0  # sample/row_finite calls reduced eagerly (other logits)

        dtype = self._act_dtype

        def _with_picks(logits):
            """``logits`` and the picks taken from the very array returned:
            each row's greedy token per codebook and its all-finite flag as
            one more column, ``(B, K + 1)`` int32 — the expressions of
            :meth:`sample` and :meth:`row_finite`, so they agree bit for bit
            (the barrier keeps the argmax off any unrounded producer)."""
            logits = jax.lax.optimization_barrier(logits)
            step = logits[:, -1]  # (B, V) or (B, K, V)
            tok = jnp.argmax(step, axis=-1).astype(jnp.int32).reshape(step.shape[0], -1)
            fin = jnp.isfinite(step.astype(jnp.float32)).reshape(step.shape[0], -1).all(axis=-1)
            return logits, jnp.concatenate([tok, fin[:, None].astype(jnp.int32)], axis=1)

        def _prefill(params, batch, pool, slot):
            with shd.use_rules(mesh, cfg.decode_policy()):
                caches = lm.make_caches(cfg, 1, max_len, dtype=dtype)
                logits, caches = lm.prefill(params, cfg, batch, caches)
                pool = jax.tree_util.tree_map(
                    lambda p, c: jax.lax.dynamic_update_slice_in_dim(
                        p, c.astype(p.dtype), slot, axis=lm._CACHE_BATCH_AXIS
                    ),
                    pool,
                    caches,
                )
                return *_with_picks(logits), pool

        def _decode(params, tokens, pool, slot_idx):
            with shd.use_rules(mesh, cfg.decode_policy()):
                logits, update = lm.decode_delta(params, cfg, tokens, pool)
                pool = lm.scatter_cache_slots(pool, update, slot_idx)
                logits = jnp.take(logits, slot_idx, axis=0, mode="clip")
                return *_with_picks(logits), pool

        self._prefill_fn = jax.jit(_prefill, donate_argnums=2)
        self._decode_fn = jax.jit(_decode, donate_argnums=2)

    # -- FAµST plumbing -----------------------------------------------------
    def _build_faust_op(self):
        """The unembedding FaustOp (decode's per-step projection) for
        advisory live-batch dispatch queries; None for dense models."""
        cfg = self.cfg
        if cfg.faust_unembed is None:
            return None
        head = self.params.get("unembed", {})
        if "faust" not in head:
            return None
        import jax

        from repro.api.operator import FaustOp
        from repro.layers.faust_linear import params_to_blockfaust

        fp = head["faust"]
        if cfg.n_codebooks > 1:  # stacked per-codebook heads: query head 0
            fp = jax.tree_util.tree_map(lambda t: t[0], fp)
        op = FaustOp.from_blockfaust(
            params_to_blockfaust(fp, cfg.faust_unembed, cfg.d_model, cfg.vocab)
        )
        if cfg.faust_unembed.shard is not None:
            op = op.with_sharding(cfg.faust_unembed.shard)
        return op

    def dispatch_for(self, batch: int):
        if self._faust_op is None:
            return None
        rep = self._dispatch_memo.get(batch)
        if rep is None:
            rep = self._faust_op.dispatch_for(batch, self._act_dtype)
            self._dispatch_memo[batch] = rep
        return rep

    def unembed_blockfaust(self):
        """The currently-published unembedding chain as a
        :class:`~repro.core.compress.BlockFaust` (None for dense models) —
        what :func:`repro.streaming.swap.hot_swap` classifies a refresh
        against."""
        cfg = self.cfg
        if cfg.faust_unembed is None or "faust" not in self.params.get(
            "unembed", {}
        ):
            return None
        from repro.layers.faust_linear import params_to_blockfaust

        return params_to_blockfaust(
            self.params["unembed"]["faust"], cfg.faust_unembed,
            cfg.d_model, cfg.vocab,
        )

    def swap_unembed(self, bf) -> None:
        """Publish a refreshed unembedding chain between engine steps.

        Functional params update (the old tree is untouched — an in-flight
        jitted call keeps its arguments) + advisory-op rebuild.  Because
        ``params`` is a per-call argument of the jitted prefill/decode
        closures, a swap whose arrays keep their shapes/dtypes reuses the
        compiled caches untouched (values-only swap); changed support
        sizes retrace on the next call — the staged re-pack.  Policy
        (classification, guard, stats) lives in
        :mod:`repro.streaming.swap` — this is only the publication
        primitive.
        """
        cfg = self.cfg
        if cfg.faust_unembed is None or "faust" not in self.params.get(
            "unembed", {}
        ):
            raise ValueError("model has no FAµST unembedding to swap")
        if cfg.n_codebooks > 1:
            raise NotImplementedError("hot-swap of stacked per-codebook heads")
        from repro.layers.faust_linear import blockfaust_to_params
        from repro.layers.param import split_annotations

        unembed = dict(self.params["unembed"])
        unembed["faust"], _ = split_annotations(blockfaust_to_params(bf))
        self.params = {**self.params, "unembed": unembed}
        self._faust_op = self._build_faust_op()
        self._dispatch_memo.clear()

    # -- Executor interface -------------------------------------------------
    def prefill_forward(self, slot: int, prompt: np.ndarray, extras: dict):
        with _span("executor.prefill"):
            return self._prefill_forward(slot, prompt, extras)

    def _prefill_forward(self, slot, prompt, extras):
        from repro.api import dispatch as _dispatch

        jnp = self._jnp
        with _span("executor.launch"):
            prompt = np.asarray(prompt)
            n = prompt.shape[-1]
            chunk = self.cfg.attn_chunk
            head, tail = prompt, prompt[..., :0]
            if n > chunk and n % chunk:
                # Chunked prefill (flash attention / SSD scan) requires
                # S % attn_chunk == 0 for S > chunk.  Re-prefills of
                # prompt+generated — the retry and evict re-admission paths —
                # arrive at ragged lengths, so prefill the aligned prefix and
                # replay the remainder through the decode step: the final
                # replayed token's logits are exactly the full prompt's
                # prefill logits (token-exact by construction).
                aligned = (n // chunk) * chunk
                head, tail = prompt[..., :aligned], prompt[..., aligned:]
            batch = {"tokens": jnp.asarray(head)[None]}
            for k, v in extras.items():
                batch[k] = jnp.asarray(v)[None]
            mark = _dispatch.last_report()
            logits, picks, self.pool = self._prefill_fn(
                self.params, batch, self.pool, jnp.asarray(slot, jnp.int32)
            )
            self._step_picks = (logits, picks, None)
            for i in range(tail.shape[-1]):
                tok = tail[..., i : i + 1][None]  # (1,1)/(1,K,1)
                logits = self._decode_pool([slot], tok)
        with _span("executor.wait"):
            logits.block_until_ready()
        if _dispatch.last_report() is not mark:  # a FAµST layer dispatched
            self.faust_dispatch = _dispatch.last_report()
        return logits

    def decode_forward(self, slots: Sequence[int], tokens: np.ndarray):
        with _span("executor.decode", "rows", len(slots), "pool_rows", self.pool_rows):
            return self._decode_forward(slots, tokens)

    def _decode_pool(self, slots, tokens):
        """One pool-wide decode step for the live rows ``slots``; returns
        their logits in ``slots`` order."""
        jnp, n, b = self._jnp, self.n_slots, len(slots)
        tokens = np.asarray(tokens)
        pool_tokens = np.zeros((n,) + tokens.shape[1:], tokens.dtype)
        pool_tokens[slots] = tokens  # free slots decode token 0, unwritten
        slot_idx = np.full(n, n, np.int32)  # padding past the pool is dropped
        slot_idx[:b] = slots
        logits, picks, self.pool = self._decode_fn(
            self.params, jnp.asarray(pool_tokens), self.pool, jnp.asarray(slot_idx)
        )
        if b < n:
            logits = logits[:b]
        self._step_picks = (logits, picks, None)
        return logits

    def _decode_forward(self, slots, tokens):
        from repro.api import dispatch as _dispatch

        with _span("executor.launch"):
            mark = _dispatch.last_report()
            logits = self._decode_pool(slots, tokens)
        with _span("executor.wait"):
            logits.block_until_ready()
        if _dispatch.last_report() is not mark:
            # decode-step decision: the steady-state serving path
            self.faust_dispatch = _dispatch.last_report()
        return logits

    def _fused(self, logits) -> bool:
        """Whether ``logits`` is the object the last step returned, whose
        program picked its tokens and flags.  Anything else — the
        ``FaultInjector``'s poisoned copy, a slice, another executor's
        logits — is reduced eagerly."""
        return self._step_picks is not None and logits is self._step_picks[0]

    def _host_picks(self, logits) -> np.ndarray:
        """The last step's picks, ``(B, K + 1)``, copied to the host on
        first use: the step's one readback."""
        held, picks, host = self._step_picks
        if host is None:
            host = np.asarray(picks)[: logits.shape[0]]
            self._step_picks = (held, picks, host)
        return host

    def sample(self, logits) -> np.ndarray:
        """Greedy argmax of the last position — same slicing contract as
        ``Server._sample`` (seq axis is axis 1 in both logits layouts)."""
        jnp = self._jnp
        fused = self._fused(logits)
        with _span("executor.sample", "fused", int(fused)):
            if fused:
                self.picks_fused += 1
                tok = self._host_picks(logits)[:, :-1]  # (B, K)
            else:
                self.picks_eager += 1
                step = logits[:, -1]  # (B, V) or (B, K, V)
                tok = jnp.argmax(step, axis=-1).astype(jnp.int32)
            if self.cfg.n_codebooks > 1:
                return np.asarray(tok.reshape(tok.shape[0], self.cfg.n_codebooks, 1))
            return np.asarray(tok.reshape(-1, 1))

    def row_finite(self, logits) -> np.ndarray:
        """Per-row all-finite mask of the last position, ``(B,)`` bool —
        the engine's NaN guard.  The last step's program reduced it
        already (read from :meth:`sample`'s host copy); other logits are
        reduced on device, so the guard moves B bools instead of the
        ``(B, V)`` logits."""
        jnp = self._jnp
        fused = self._fused(logits)
        with _span("executor.row_finite", "fused", int(fused)):
            if fused:
                self.picks_fused += 1
                return self._host_picks(logits)[:, -1].astype(bool)
            self.picks_eager += 1
            step = logits[:, -1].astype(jnp.float32)  # (B, V) or (B, K, V)
            fin = jnp.isfinite(step).reshape(step.shape[0], -1).all(axis=-1)
            return np.asarray(fin)

    def free(self, slot: int) -> None:
        # A free row is decoded with a dummy token but never written, its
        # logits are dropped, and a reuse prefill overwrites the row and
        # its pos — nothing to scrub.
        return None


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class Engine:
    """Continuous-batching scheduler over an :class:`Executor`.

    ``clock`` is injectable (``tests/engine_sim.FakeClock``) so the whole
    scheduler — admission order, slot schedule, stats — is deterministic
    under test with zero wall-clock dependence.

    Supervision policy (all keyword-only; ``None`` ⇒ env default):

    * ``retry_budget`` / ``backoff_s`` — a raised forward preempts the
      affected requests through the eviction path; each gets at most
      ``retry_budget`` retries (env ``REPRO_RETRY_BUDGET``, default 2)
      with exponential backoff ``backoff_s · 2^(n_retries−1)`` (env
      ``REPRO_RETRY_BACKOFF``, default 0.05 s) before terminal FAILED.
    * ``max_evictions`` — starvation guard: a request evicted this many
      times is pinned to its slot (env ``REPRO_MAX_EVICTIONS``, default
      8; ``<= 0`` disables the cap).
    * ``max_queue`` — admission control: submissions beyond this queue
      depth are shed as terminal REJECTED (default unbounded).
    * ``default_ttl`` — deadline applied to every submit that does not
      pass its own ``ttl`` (default none).
    * ``nan_guard`` — per-stream quarantine of non-finite logits rows
      (default on; ``LMExecutor`` reduces it inside the step program).
    * ``sleep`` — how the engine waits out retry backoff when nothing is
      live (default: ``clock.advance`` when the clock has one — the sim
      FakeClock — else ``time.sleep``).
    """

    def __init__(
        self,
        executor: Executor,
        clock: Callable[[], float] = time.monotonic,
        *,
        retry_budget: int | None = None,
        backoff_s: float | None = None,
        max_queue: int | None = None,
        default_ttl: float | None = None,
        max_evictions: int | None = None,
        nan_guard: bool = True,
        sleep: Callable[[float], None] | None = None,
    ):
        self.executor = executor
        self.clock = clock
        self.allocator = SlotAllocator(executor.n_slots)
        self.queue: deque[Request] = deque()
        self.running: "OrderedDict[str, Request]" = OrderedDict()
        self.done: dict[str, Request] = {}
        self.stats = EngineStats()
        self._n = 0
        self._ticks = 0  # Engine.step calls, the engine.step span's arg
        # -- supervision policy --
        if retry_budget is None:
            retry_budget = int(os.environ.get("REPRO_RETRY_BUDGET", "2"))
        if backoff_s is None:
            backoff_s = float(os.environ.get("REPRO_RETRY_BACKOFF", "0.05"))
        if max_evictions is None:
            max_evictions = int(os.environ.get("REPRO_MAX_EVICTIONS", "8"))
        self.retry_budget = retry_budget
        self.backoff_s = backoff_s
        self.max_evictions = max_evictions if max_evictions > 0 else None
        self.max_queue = max_queue
        self.default_ttl = default_ttl
        self.nan_guard = nan_guard
        if sleep is None:
            sleep = getattr(clock, "advance", None) or time.sleep
        self._sleep = sleep
        # Fast-path guards: a zero-fault, zero-deadline run must make
        # exactly the same clock() calls as the pre-supervision engine
        # (byte-identical stats under FakeClock) — so deadline sweeps and
        # backoff scans only run when something armed them.
        self._n_deadlines = 0  # non-terminal requests carrying a deadline
        self._maybe_blocked = False  # a queued request may be in backoff

    # -- submission / results ----------------------------------------------
    def submit(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        extras: dict | None = None,
        rid: str | None = None,
        *,
        ttl: float | None = None,
    ) -> str:
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if rid is None:
            rid = f"r{self._n}"
        self._n += 1
        if rid in self.done or rid in self.running or any(
            r.rid == rid for r in self.queue
        ):
            raise ValueError(f"duplicate rid {rid!r}")
        arrival = self.clock()
        req = Request(
            rid=rid,
            prompt=np.asarray(prompt),
            max_new_tokens=int(max_new_tokens),
            extras=dict(extras or {}),
            arrival=arrival,
        )
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            # load shedding at the door: terminal REJECTED, never queued —
            # result() raises and the caller decides whether to resubmit
            req.state = REJECTED
            req.error = (
                f"queue depth {len(self.queue)} >= max_queue {self.max_queue}"
            )
            req.done_t = arrival
            self.done[rid] = req
            self.stats.rejected += 1
            return rid
        if ttl is None:
            ttl = self.default_ttl
        if ttl is not None:
            req.deadline = arrival + float(ttl)
            self._n_deadlines += 1
        self.queue.append(req)
        return rid

    def result(self, rid: str) -> np.ndarray:
        req = self.done.get(rid)
        if req is None:
            raise KeyError(f"request {rid!r} is not finished")
        if req.state != DONE:
            raise RuntimeError(f"request {rid!r} {req.state}: {req.error}")
        return req.output()

    def status(self, rid: str) -> str:
        """Current lifecycle state of ``rid`` (see module constants)."""
        if rid in self.done:
            return self.done[rid].state
        if rid in self.running:
            return RUNNING
        if any(r.rid == rid for r in self.queue):
            return QUEUED
        raise KeyError(f"unknown request {rid!r}")

    @property
    def n_pending(self) -> int:
        return len(self.queue) + len(self.running)

    # -- scheduling ---------------------------------------------------------
    def step(self) -> list[str]:
        """One scheduler tick: admit while slots are free, then one decode
        step over the live batch.  Returns rids finished this tick."""
        self._ticks += 1
        with _span("engine.step", "step", self._ticks):
            finished: list[str] = []
            if self._n_deadlines:
                self._expire(finished)
            self._admit(finished)
            live = self._live_by_slot()
            if live:
                with _span("engine.decode", "rows", len(live)):
                    self._decode(live, finished)
            elif self.queue and self._maybe_blocked:
                # nothing live and every queued request is in retry backoff:
                # wait out the earliest not_before so run() cannot spin
                now = self.clock()
                wait = min(r.not_before for r in self.queue) - now
                if wait > 0:
                    self._sleep(wait)
            return finished

    def run(self, max_steps: int | None = None) -> list[str]:
        """Step until every submitted request has finished."""
        finished: list[str] = []
        steps = 0
        while self.n_pending:
            finished.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return finished

    def evict(self, rid: str, force: bool = False) -> bool:
        """Preempt a live request: free its slot and put it back near the
        *front* of the queue.  Re-admission prefills prompt+generated, so
        the greedy stream continues token-exactly.

        Starvation guard: once a request has been evicted
        ``max_evictions`` times it is pinned — ``evict`` refuses and
        returns False (``force=True`` overrides), so a short stream under
        constant preemption pressure still finishes.  Re-queued
        preemptees are age-ordered (see :meth:`_requeue`)."""
        req = self.running.get(rid)
        if req is None:
            raise KeyError(f"request {rid!r} is not running")
        if (
            not force
            and self.max_evictions is not None
            and req.n_evictions >= self.max_evictions
        ):
            return False
        self.running.pop(rid)
        self.allocator.free(req.slot)
        self.executor.free(req.slot)
        req.slot = None
        req.state = QUEUED
        req.n_evictions += 1
        self._requeue(req)
        self.stats.evicted += 1
        return True

    # -- internals ----------------------------------------------------------
    def _live_by_slot(self) -> list[Request]:
        # Batch rows ordered by slot index: with the lowest-free-slot
        # allocator this makes row order a deterministic function of the
        # schedule (and independent of dict iteration history).
        return sorted(self.running.values(), key=lambda r: r.slot)

    def _admit(self, finished: list[str]) -> None:
        while self.queue and self.allocator.n_free:
            req = self._pop_admissible()
            if req is None:  # every queued request is in retry backoff
                return
            prompt = req.prompt_full()
            with _span("engine.admit", "rid", req.rid, "tokens", prompt.shape[-1]):
                if not self._admit_one(req, prompt, finished):
                    return  # let the backoff elapse before re-admitting

    def _admit_one(self, req: Request, prompt: np.ndarray, finished: list[str]) -> bool:
        """Prefill ``req`` into a fresh slot and sample its first token;
        False when the prefill raised (admission pauses for the backoff)."""
        req.slot = self.allocator.alloc(req.rid)
        notify = getattr(self.executor, "on_admit", None)
        if notify is not None:  # e.g. FaultInjector slot→rid tracking
            notify(req.rid, req.slot)
        self.stats.admitted += 1
        t0 = self.clock()
        try:
            logits = self.executor.prefill_forward(req.slot, prompt, req.extras)
        except Exception as exc:  # noqa: BLE001 — supervision boundary
            t1 = self.clock()
            self.stats.prefill_s += t1 - t0
            # ran=False: the fault fired before the executor touched
            # the row, so only the allocator slot is reclaimed
            self._step_failure([req], exc, t1, finished, ran=False)
            return False
        t1 = self.clock()
        self.stats.prefill_s += t1 - t0
        tok = self.executor.sample(logits)  # (1, 1) / (1, K, 1)
        t2 = self.clock()
        # the prefill-sampled token is a decoded token: count it and
        # its sampling time (the old ServeStats excluded both)
        self.stats.decode_s += t2 - t1
        if self.nan_guard:
            bad = self._bad_rows(logits)
            if bad is not None and bad[0]:
                self.stats.quarantined += 1
                self._finish_terminal(
                    req, FAILED,
                    "non-finite prefill logits (stream quarantined)",
                    t2, finished,
                )
                return True
        self._append_token(req, np.asarray(tok[0]))
        if req.first_token_t is None:
            req.first_token_t = t2
            self.stats.ttft_s[req.rid] = t2 - req.arrival
        req.state = RUNNING
        self.running[req.rid] = req
        if len(req.generated) >= req.max_new_tokens:
            self._complete(req, t2, finished)
        return True

    def _decode(self, live: list[Request], finished: list[str]) -> None:
        slots = [r.slot for r in live]
        tokens = np.stack([r.last_token for r in live])  # (B,1)/(B,K,1)
        b = len(live)
        self.stats.steps += 1
        self.stats.queue_depth_max = max(self.stats.queue_depth_max, len(self.queue))
        self.stats.queue_depth_sum += len(self.queue)
        self.stats.occupancy[b] = self.stats.occupancy.get(b, 0) + 1
        self.stats.decode_masked_rows += getattr(self.executor, "pool_rows", b) - b
        with _span("engine.dispatch_query"):
            self.stats.dispatch_by_batch[b] = self.executor.dispatch_for(b)
        t0 = self.clock()
        try:
            logits = self.executor.decode_forward(slots, tokens)
            toks = self.executor.sample(logits)  # (B,1)/(B,K,1)
        except Exception as exc:  # noqa: BLE001 — supervision boundary
            t1 = self.clock()
            self.stats.decode_s += t1 - t0
            self._step_failure(live, exc, t1, finished, ran=True)
            return
        t1 = self.clock()
        self.stats.decode_s += t1 - t0
        bad = self._bad_rows(logits) if self.nan_guard else None
        for i, req in enumerate(live):
            if bad is not None and bad[i]:
                # divergence quarantine: fail this stream, not the batch
                self.stats.quarantined += 1
                self._finish_terminal(
                    req, FAILED,
                    "non-finite logits (stream quarantined)", t1, finished,
                )
                continue
            self._append_token(req, np.asarray(toks[i]))
            if len(req.generated) >= req.max_new_tokens:
                self._complete(req, t1, finished)
        self._note_dispatch()

    # -- supervision internals ----------------------------------------------
    def _note_dispatch(self) -> None:
        rep = getattr(self.executor, "faust_dispatch", None)
        if rep is None:
            rep = self.stats.faust_dispatch
        elif rep is not self.stats.faust_dispatch and getattr(
            rep, "demoted_from", None
        ):
            # a newly staged computation ran on a demoted backend
            self.stats.demotions += 1
        self.stats.faust_dispatch = rep

    def _bad_rows(self, logits) -> np.ndarray | None:
        """Non-finite mask over the batch rows of one step's logits, or
        None when every row is finite (the overwhelmingly common case).
        Executors may provide ``row_finite`` (device-side reduction)."""
        fn = getattr(self.executor, "row_finite", None)
        if fn is not None:
            finite = np.asarray(fn(logits))
        else:
            step = np.asarray(logits[:, -1], dtype=np.float32)
            finite = np.isfinite(step).reshape(step.shape[0], -1).all(axis=-1)
        bad = ~finite
        return bad if bad.any() else None

    def _pop_admissible(self) -> Request | None:
        """Next queued request whose retry backoff (``not_before``) has
        elapsed; None when all are still blocked.  The fast path — no
        request ever retried — pops the head with no clock read."""
        if not self._maybe_blocked:
            return self.queue.popleft()
        now = self.clock()
        self._maybe_blocked = any(r.not_before > now for r in self.queue)
        for i, req in enumerate(self.queue):
            if req.not_before <= now:
                del self.queue[i]
                return req
        return None

    def _requeue(self, req: Request) -> None:
        """Return a preempted/retried request near the front of the
        queue, age-ordered among the other preemptees already there
        (oldest arrival first) — so one unlucky stream cannot be starved
        behind a churn of younger evictees.  A single evictee into a
        fresh queue degenerates to ``appendleft`` (the PR 7 behaviour)."""
        i = 0
        while (
            i < len(self.queue)
            and (self.queue[i].n_evictions or self.queue[i].n_retries)
            and self.queue[i].arrival <= req.arrival
        ):
            i += 1
        self.queue.insert(i, req)

    def _step_failure(
        self,
        reqs: list[Request],
        exc: Exception,
        now: float,
        finished: list[str],
        *,
        ran: bool,
    ) -> None:
        """A forward raised: preempt every affected request through the
        eviction path (re-prefill of prompt+generated keeps retried
        streams token-exact), with exponential backoff and a per-request
        retry budget; over-budget requests turn terminal FAILED.
        ``ran=False`` ⇒ the executor never touched the rows (fault fired
        pre-launch), so only the allocator slots are reclaimed."""
        for req in reqs:
            self.running.pop(req.rid, None)
            if req.slot is not None:
                self.allocator.free(req.slot)
                if ran:
                    self.executor.free(req.slot)
                req.slot = None
            if req.n_retries < self.retry_budget:
                req.n_retries += 1
                self.stats.retries += 1
                req.state = QUEUED
                req.not_before = now + self.backoff_s * (
                    2 ** (req.n_retries - 1)
                )
                self._maybe_blocked = True
                self._requeue(req)
            else:
                self._finish_terminal(
                    req, FAILED,
                    f"{type(exc).__name__}: {exc} "
                    f"(retry budget {self.retry_budget} exhausted)",
                    now, finished,
                )

    def _expire(self, finished: list[str]) -> None:
        """Sweep deadlines: expired running requests free their slot,
        expired queued requests are shed — both terminal TIMED_OUT.
        Only called when ``_n_deadlines`` is non-zero (one clock read)."""
        now = self.clock()
        expired = [
            r for r in self.running.values()
            if r.deadline is not None and now > r.deadline
        ]
        for req in expired:
            self._finish_terminal(
                req, TIMED_OUT,
                f"deadline exceeded after {now - req.arrival:.4g}s",
                now, finished,
            )
        if any(r.deadline is not None and now > r.deadline for r in self.queue):
            keep: deque[Request] = deque()
            for req in self.queue:
                if req.deadline is not None and now > req.deadline:
                    self._finish_terminal(
                        req, TIMED_OUT,
                        f"shed from queue after {now - req.arrival:.4g}s",
                        now, finished,
                    )
                else:
                    keep.append(req)
            self.queue = keep

    def _finish_terminal(
        self,
        req: Request,
        state: str,
        error: str,
        now: float,
        finished: list[str],
    ) -> None:
        """Move a request to a terminal non-DONE state, releasing its
        slot if it holds one.  ``result()`` for it raises RuntimeError."""
        if req.slot is not None:
            self.allocator.free(req.slot)
            self.executor.free(req.slot)
            req.slot = None
        self.running.pop(req.rid, None)
        req.state = state
        req.error = error
        req.done_t = now
        if req.deadline is not None:
            self._n_deadlines -= 1
            req.deadline = None
        self.done[req.rid] = req
        if state == FAILED:
            self.stats.failed += 1
        elif state == TIMED_OUT:
            self.stats.timed_out += 1
        finished.append(req.rid)

    def _append_token(self, req: Request, tok: np.ndarray) -> None:
        req.generated.append(tok)
        req.last_token = tok
        self.stats.tokens_decoded += 1

    def _complete(self, req: Request, now: float, finished: list[str]) -> None:
        self.allocator.free(req.slot)
        self.executor.free(req.slot)
        req.slot = None
        req.state = DONE
        req.done_t = now
        if req.deadline is not None:
            self._n_deadlines -= 1
            req.deadline = None
        self.running.pop(req.rid, None)
        self.done[req.rid] = req
        self.stats.completed += 1
        n = len(req.generated)
        if n > 1:
            self.stats.tpot_s[req.rid] = (now - req.first_token_t) / (n - 1)
        else:
            self.stats.tpot_s[req.rid] = 0.0
        finished.append(req.rid)
