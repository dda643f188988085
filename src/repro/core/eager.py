"""Identity caches for host-side work that eager applies would repeat.

Packing a chain, unpacking it, or assembling a kernel's step table is pure
host/XLA work on arrays that do not change between applies of one
operator.  Under ``jax.jit`` it is staged once per trace and costs nothing
per call; eagerly it would run on every apply.  :func:`cached` memoizes it
per owner *identity* (a weakref guards against ``id()`` reuse).

Under any active trace the work is rebuilt instead: its results would be
tracers bound to that trace, and a cached tracer leaks into later traces
(``UnexpectedTracerError``).
"""
from __future__ import annotations

import weakref
from typing import Callable, TypeVar

import jax

T = TypeVar("T")


def is_eager(*arrays) -> bool:
    """Whether no JAX trace is active and no argument is a tracer."""
    return jax.core.trace_ctx.is_top_level() and not any(
        isinstance(a, jax.core.Tracer) for a in arrays
    )


def cached(
    store: dict,
    maxsize: int,
    owner,
    key: tuple,
    build: Callable[[], T],
    *arrays,
) -> T:
    """``build()`` memoized in ``store`` per ``(id(owner),) + key``.

    ``arrays`` are the inputs ``build`` reads; when any is a tracer, or a
    trace is active, ``build()`` runs uncached.  The oldest entry is
    evicted once ``store`` holds ``maxsize`` entries.
    """
    if not is_eager(owner, *arrays):
        return build()
    k = (id(owner),) + key
    ent = store.get(k)
    if ent is not None and ent[0]() is owner:
        return ent[1]
    val = build()
    if len(store) >= maxsize:
        store.pop(next(iter(store)))
    store[k] = (weakref.ref(owner), val)
    return val
