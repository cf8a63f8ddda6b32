"""Names for what the engine does, on the profiler's clock.

Three kinds of names, one vocabulary each:

- **Device phases** (``phase(name)``): a ``jax.named_scope`` around the
  code of one phase of the superstep.  A scope only writes the HLO
  ``op_name`` metadata of the ops traced inside it (``.../bsp.gather/...``)
  and adds no op, so the compiled program runs the same ops with or without
  a profiler.  An op's phase is the last component of its ``op_name`` that
  is in :data:`PHASES`; a device trace names each op by its HLO
  instruction, whose metadata the trace keeps with the module.  A fusion
  carries its root op's ``op_name``, so a fusion that spans two phases
  counts under the root's.  :data:`ELL` is a container scope around the
  whole ELL leg (gather, padding and kernel), whichever phases it holds
  inside.
- **Host spans** (``span(name)``): one ``jax.profiler.TraceAnnotation``,
  written into the profiler's trace on the same clock as the device ops,
  that also adds its call count, its total seconds and its self seconds
  (total less the time of the spans nested in it, on the same thread) to
  one in-memory table keyed by name.  ``snapshot()`` copies the table.
- **Counters** (``count(name, **amounts)``): host-side sums of what the
  program built, such as the sliced ELL's gathered slots
  (:data:`ELL_PACK`); ``snapshot()`` shows each beside the spans, as its
  call count and each amount's sum.

All are always on: there is no switch.  With no profiler running a span
costs a clock read and a lock, and a phase nothing at run time.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict

import jax

# Phases of a superstep (docs/superstep.md, "Tracing").
PHASES = (
    "bsp.gather",     # each edge's source value (edge gather, ELL gather)
    "bsp.reduce",     # combining per destination: segment ops, kernels
    "bsp.exchange",   # outbox -> inbox, the inbox reduce, collectives
    "bsp.apply",      # apply_fn, the finish vote, freezing finished queries
    "bsp.layout",     # hybrid: partition layout <-> degree-ranked ids
    "bsp.direction",  # frontier density, the push/pull vote, work counters
)
ELL = "bsp.ell"

# Host spans.
STATE_INIT = "repro.state_init"   # an algorithm's initial state, device put
EXECUTE = "repro.execute"         # BSPEngine.execute
WAIT = "repro.wait"               # a host read that waits for the device
FETCH = "repro.fetch"             # a result back in global vertex order
HYBRID_SPLIT = "repro.hybrid.split"   # one degree split (BSPEngine)
SPANS = (STATE_INIT, EXECUTE, WAIT, FETCH, HYBRID_SPLIT)

# Counters.
ELL_PACK = "repro.ell.pack"   # per split build: ELL slots gathered per
                              # query and superstep, real ELL non-zeros
COUNTERS = (ELL_PACK,)

_lock = threading.Lock()
_table: Dict[str, list] = {}       # name -> [count, total_s, self_s]
_counters: Dict[str, Dict[str, float]] = {}   # name -> {"count", amounts}
_local = threading.local()         # .stack: child seconds of open spans


def phase(name: str):
    """``jax.named_scope(name)`` for a name of :data:`PHASES` or
    :data:`ELL`."""
    if name not in PHASES and name != ELL:
        raise ValueError(f"unknown phase {name!r}; phases are {PHASES} and "
                         f"{ELL!r}")
    return jax.named_scope(name)


@contextlib.contextmanager
def span(name: str):
    """Time the block as the host span ``name`` (also a decorator)."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; spans are {SPANS}")
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    stack.append(0.0)
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        total = time.perf_counter() - start
        children = stack.pop()
        if stack:
            stack[-1] += total
        with _lock:
            row = _table.setdefault(name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += total
            row[2] += total - children


def count(name: str, **amounts: float) -> None:
    """Add one call and each of ``amounts`` to the counter ``name``."""
    if name not in COUNTERS:
        raise ValueError(f"unknown counter {name!r}; counters are "
                         f"{COUNTERS}")
    with _lock:
        row = _counters.setdefault(name, {"count": 0})
        row["count"] += 1
        for key, amount in amounts.items():
            row[key] = row.get(key, 0) + amount


def snapshot() -> Dict[str, Dict[str, float]]:
    """``{name: {"count", "total_s", "self_s"}}`` of every span so far in
    this process, and ``{name: {"count", <amount>...}}`` of every counter:
    a copy, which later spans and counts leave unchanged."""
    with _lock:
        out = {name: {"count": count, "total_s": total, "self_s": own}
               for name, (count, total, own) in _table.items()}
        out.update((name, dict(row)) for name, row in _counters.items())
        return out
