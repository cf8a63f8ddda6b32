"""The TOTEM BSP engine in JAX (paper §4).

Each BSP superstep is exactly the paper's cycle:

  1. **compute**  — every partition runs the algorithm's edge kernel on its
     edges; messages to local destinations and to outbox slots are reduced in
     a single ``segment_min``/``segment_sum`` over the extended destination
     index (source-side message reduction, §3.4, is implicit here — multiple
     local edges to the same remote vertex share one outbox slot).
  2. **communicate** — outboxes are exchanged with the symmetric inboxes of
     the peer partitions (paper Fig. 6).  Locally this is a transpose;
     distributed it is an ``all_to_all`` over the mesh axis (ICI = the PCI-E
     analogue).
  3. **scatter** — the user combine (``alg_scatter``) folds inbox messages
     into local vertex state.
  4. **apply + vote** — per-vertex update; all partitions vote to finish
     (paper "Termination").

The same superstep body runs in two modes:
  - *local*: all P partitions stacked on one device (tests, small graphs);
  - *distributed*: partitions sharded over a mesh axis with ``shard_map``
    (one partition per device; this is the multi-pod scale-out path).

**Query batching.**  Every internal superstep path operates on state whose
leaves carry a leading *query axis* ``Q``: vertex leaves are
``[Q, Pl, v_max]``, per-partition scalars ``[Q, Pl]``.  The graph topology
(edge arrays, block metadata, outbox maps, degree splits) is shared across
the batch — only message values and state grow with Q — so a batch of Q
concurrent traversals (multi-source BFS/SSSP/BC, personalized PageRank)
amortizes one resident partitioned graph, one compiled ``lax.while_loop``,
and one kernel-launch sequence over all queries.  Each query votes finish
independently; converged queries are masked out of the apply step (their
state freezes bitwise) while the rest continue, and ``run_batched`` reports
per-query superstep counts.  The single-query ``run``/``run_fixed`` API is
preserved as a Q=1 wrapper.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.runtime import chaos
from repro.core.partition import (BlockMetadata, EdgeArrays, PartitionedGraph,
                                  build_block_metadata)

Array = jax.Array
State = Any    # pytree of [Pl, v_max]-leading arrays + [Pl] scalars
BatchedState = Any  # same pytree with a leading query axis: [Q, Pl, ...]


def batch_state(state: State) -> BatchedState:
    """Add a Q=1 query axis to every leaf (single-query compatibility)."""
    return jax.tree.map(lambda x: jnp.asarray(x)[None], state)


def unbatch_state(state: BatchedState) -> State:
    """Strip the query axis of a Q=1 batched state."""
    return jax.tree.map(lambda x: x[0], state)


def num_queries(state: BatchedState) -> int:
    """Static query-batch extent Q of a batched state pytree."""
    return int(jax.tree_util.tree_leaves(state)[0].shape[0])

SUM = "sum"
MIN = "min"
_SEGMENT_OP = {SUM: jax.ops.segment_sum, MIN: jax.ops.segment_min}
_COMBINE = {SUM: jnp.add, MIN: jnp.minimum}


@dataclasses.dataclass(frozen=True)
class EdgeMessage:
    """Elementwise edge-message form of ``edge_fn`` the fused kernel inlines.

    ``fn(vals, weight, step, consts) -> msgs`` where ``vals`` maps each key
    in ``gather`` to that state array's value at the edge's *source* vertex,
    ``weight`` is the per-edge weight (present iff ``use_weight``), ``step``
    is the superstep as float32, and ``consts`` maps each key in ``consts``
    to a per-partition scalar state entry (e.g. BC's ``max_level``).  The
    function must be elementwise/broadcast-safe: the kernel calls it on
    [block_e]-shaped values, the fallback on [Pl, e_max]-shaped ones, and it
    must compute exactly what ``edge_fn`` computes per edge.

    ``weight_op`` declares how the weight enters the message, so SpMV-style
    backends can factor it out of the per-source part:
    ``fn(vals, w, ...) == fn(vals, ident, ...) ⊗ w`` with (⊗, ident) =
    ``("add", 0)`` for min-combines or ``("mul", 1)`` for sum-combines.
    Required (and only meaningful) when ``use_weight`` — it makes the program
    eligible for the hybrid degree-split backend, which runs the edge as a
    semiring SpMV (min_plus / plus_times) instead of per-edge messages.
    """

    gather: Tuple[str, ...]
    fn: Callable[..., Array]
    consts: Tuple[str, ...] = ()
    use_weight: bool = False
    weight_op: Optional[str] = None   # None | "add" | "mul"
    # True iff every non-identity message of one superstep carries the SAME
    # value (BFS: all frontier vertices send step+1).  Licenses the
    # bottom-up kernel's per-row early exit as *exact* — the first live
    # parent's value IS the row minimum.  Programs whose messages differ per
    # source (CC labels, SSSP distances) must leave this False; their pull
    # steps scan full rows.
    frontier_uniform: bool = False


@dataclasses.dataclass(frozen=True)
class IncrementalForm:
    """A program's warm-start form for incremental recomputation.

    ``program`` is the *relaxation* restatement of the algorithm — one whose
    fixpoint is reachable by descent from any over-approximation, not just
    from the cold initial state (e.g. BFS's level-synchronous frontier test
    becomes an active-set min-relaxation over levels).  ``seed(prev_state,
    dirty)`` rebuilds the warm initial state from a previous *fixpoint* and
    a ``[Pl, v_max]`` dirty-vertex mask (the sources of edges inserted since
    that fixpoint was computed).

    Valid only while mutations stay **monotone** for the program's semiring
    (insert-only for min/min-plus: new edges can only lower the least
    fixpoint, so the old solution is a sound over-approximation — and every
    old path survives, which is what makes the warm fixpoint *bitwise* equal
    to the cold one).  Deletions, and non-monotone programs (PageRank, BC),
    must fall back to cold recompute; ``BSPEngine.run_incremental`` returns
    None when no form exists and ``DynamicGraph.dirty_since`` reports
    whether the mutation window was monotone.
    """

    program: "VertexProgram"
    seed: Callable[[BatchedState, Array], BatchedState]


@dataclasses.dataclass(frozen=True)
class VertexProgram:
    """An algorithm in TOTEM's callback form (paper Fig. 5).

    ``edge_fn(state, src, weight, step) -> msgs [Pl, e_max]`` — the per-edge
    part of ``alg_compute`` (messages for inactive sources must be the
    combine identity).
    ``apply_fn(state, acc, step) -> (new_state, finished)`` — the per-vertex
    part of ``alg_compute`` + ``alg_scatter``'s state update; ``acc`` is the
    fully-reduced [Pl, v_max] accumulator (local + remote contributions).
    ``finished`` is this shard's vote to terminate.
    ``edge_msg`` — optional :class:`EdgeMessage` equivalent of ``edge_fn``;
    programs that provide it are eligible for the fused superstep path.
    ``incremental`` — optional :class:`IncrementalForm` enabling
    ``BSPEngine.run_incremental`` warm starts after monotone mutations.
    """

    combine: str
    edge_fn: Callable[[State, Array, Optional[Array], Array], Array]
    apply_fn: Callable[[State, Array, Array], Tuple[State, Array]]
    max_steps: int = 1 << 30
    use_reverse: bool = False
    edge_msg: Optional[EdgeMessage] = None
    incremental: Optional[IncrementalForm] = None


def gather_src(x: Array, src: Array) -> Array:
    """Fetch per-edge source-vertex state: [Pl, v_max] × [Pl, e_max]."""
    return jnp.take_along_axis(x, src, axis=1)


@dataclasses.dataclass(frozen=True)
class _Dims:
    num_parts: int       # global partition count P
    v_max: int
    e_max: int
    o_max: int

    @property
    def seg(self) -> int:  # extended segment space per partition
        return self.v_max + 1 + self.num_parts * self.o_max


@dataclasses.dataclass(frozen=True)
class FusedConfig:
    """Static geometry of one direction's fused compute phase."""

    span: int            # lane-aligned distinct-segment bound per block
    block_e: int
    max_span: int = 4096
    interpret: Optional[bool] = None


# ---------------------------------------------------------------------------
# Direction-optimized traversal (docs/traversal.md)
#
# For min-combine programs a superstep can run top-down ("push": every
# frontier vertex scatters along its out-edges) or bottom-up ("pull": every
# destination row scans its in-neighbours, with early exit when messages are
# uniform).  Both directions reduce the same value multiset per destination
# under a min ⊕ — rounding-free and order-independent — so direction is
# purely a performance choice and results stay bitwise identical.
#
# The decision state rides IN the traced carry as three [Q, P] int32 leaves
# (direction, edges-examined counter, switch counter), injected by
# ``BSPEngine.execute`` and stripped before the user sees the state.  Because
# the direction is a *value*, switching mid-run never retraces: one compiled
# superstep contains both branches under ``lax.cond``.  Under ``shard_map``
# each shard sees its local [Q, pl] slice and votes from its own frontier
# density — the per-shard switching of the issue — writing its counters into
# local column 0, so a global axis-1 sum aggregates per query.
# ---------------------------------------------------------------------------

_DOPT_KEYS = ("_dopt_dir", "_dopt_edges", "_dopt_switch")
_DIR_PUSH = 0
_DIR_PULL = 1


@dataclasses.dataclass(frozen=True)
class _DoptCfg:
    """Static direction config for the reference/fused superstep closure."""

    semiring: str                 # "min" | "min_plus"
    uniform: bool                 # EdgeMessage.frontier_uniform
    forced: Optional[int] = None  # None = auto, else _DIR_PUSH/_DIR_PULL
    interpret: Optional[bool] = None


def _dopt_strip(state: State):
    """Split the dopt leaves out of the carry before user code sees it."""
    if _DOPT_KEYS[0] not in state:
        return state, None
    user = {k: v for k, v in state.items() if k not in _DOPT_KEYS}
    return user, {k: state[k] for k in _DOPT_KEYS}


def _dopt_fold(dopt: dict, want: Array, cnt: Array) -> dict:
    """Fold one superstep's decisions into the carried dopt leaves.

    ``want [Q]`` is this superstep's direction, ``cnt [Q]`` the edges the
    chosen direction examined (the deterministic work model).  Writes land
    in local column 0 — per-shard columns of the global [Q, P] leaf under
    ``shard_map`` — and the direction broadcasts across local columns."""
    prev = dopt["_dopt_dir"][:, 0]
    sw = jnp.logical_and(prev >= 0, prev != want).astype(jnp.int32)
    return {
        "_dopt_dir": jnp.broadcast_to(want[:, None].astype(jnp.int32),
                                      dopt["_dopt_dir"].shape),
        "_dopt_edges": dopt["_dopt_edges"].at[:, 0].add(cnt),
        "_dopt_switch": dopt["_dopt_switch"].at[:, 0].add(sw),
    }


def _direction_select(want: Array, run_push, run_pull, x):
    """Run push/pull per the [Q] direction vector.

    Homogeneous batches take a single branch through nested ``lax.cond``;
    mixed batches compute both and select per query.  Branch fns map
    ``x -> (y, push_cnt [Q], pull_cnt [Q])`` with identical shapes."""
    def mixed(x):
        y_p, cp, _ = run_push(x)
        y_l, _, sl = run_pull(x)
        sel = (want == _DIR_PULL)
        shape = (-1,) + (1,) * (y_p.ndim - 1)
        zero = jnp.zeros_like(cp)
        return (jnp.where(sel.reshape(shape), y_l, y_p),
                jnp.where(sel, zero, cp), jnp.where(sel, sl, zero))

    # The branches' own phases nest inside: what they leave unscoped (the
    # work counters, the per-query select) is the direction's.
    with obs.phase("bsp.direction"):
        return jax.lax.cond(
            jnp.all(want == _DIR_PUSH), run_push,
            lambda x: jax.lax.cond(jnp.all(want == _DIR_PULL),
                                   run_pull, mixed, x),
            x)


def _dopt_want(forced: Optional[int], density: Array, unvisited: Array,
               threshold) -> Array:
    """Per-query direction vote — the α-style two-term crossover.

    Pull pays one scan per destination row, early-exiting at the first
    live parent, so it wins only when (a) the frontier is dense enough
    that rows exit after ~1/density slots (the fitted ``threshold`` —
    perf_model.fit_pull_threshold's sqrt(γ/deg) crossover) AND (b) the
    frontier outweighs the *unvisited* mass: rows whose value is still
    the ⊕-identity have no live parent yet, never early-exit, and pay
    their full in-degree every pull superstep — on directed graphs the
    unreachable tail would otherwise be rescanned forever (Beamer's
    m_f > m_u/α switch, degree-uniform proxy with α = 1).  Sum combines
    never reach this vote; for min combines both directions are bitwise
    so the vote is a pure perf choice.
    """
    if forced is not None:
        return jnp.full(density.shape, forced, jnp.int32)
    pull = jnp.logical_and(density >= threshold, density > unvisited)
    return jnp.where(pull, _DIR_PULL, _DIR_PUSH).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class _HybridCfg:
    """Static geometry of one hybrid degree-split direction.

    The array payload travels separately (an ``arrs`` dict with keys
    ``dense``/``ell_col``/``ell_val``/``slot``/``hid`` and optionally
    ``push_src``/``push_dst``/``push_w``/``ell_kreal``): numpy in the
    static engine — per-trace constants, the ELL sliced with its chunk
    widths in ``ell_width`` (``HybridGraph.sliced``) — but **traced jit
    arguments** in the dynamic engine, whose ELL stays rectangular, so
    in-place edge mutations (core/dynamic.py) update the split without
    retracing and compaction can never be served from a stale compiled
    constant.
    """

    semiring: str
    k_dense: int
    num_vertices: int
    pull_threshold: float
    interpret: Optional[bool]
    # direction-optimization statics (docs/traversal.md): forced direction
    # (None = auto crossover), message uniformity (licenses the bottom-up
    # early exit), and the static dense-stage work charge k_dense².
    forced: Optional[int] = None
    uniform: bool = False
    e_dense: int = 0


def _superstep_hybrid(program: VertexProgram, cfg: _HybridCfg, arrs: dict,
                      all_finished: Callable[[Array], Array],
                      state: State, step: Array) -> Tuple[State, Array]:
    """One BSP superstep through the degree-split two-engine backend.

    The compute phase is a semiring SpMV over the *whole* graph in hybrid
    (degree-ranked) id space — dense H×H block on the MXU path, ELL remainder
    on the VPU path (core/hybrid.py).  There is no outbox/inbox: an on-chip
    split has no partition boundary to communicate across, exactly the
    paper's single-node hybrid setting (§6).  For min combines a
    frontier-density switch picks the push direction (gather + segment-min —
    cheap when few vertices send) or the pull direction (frontier-oblivious
    SpMV), the direction-optimized traversal of Sallinen et al.

    ``slot``/``hid`` in ``arrs`` translate between the engine's [P, v_max]
    partition layout and the split's degree-ranked global id space (sink =
    n for padding slots); ``push_*`` absent disables the direction switch
    (sum combines, or ``direction_switch=False``).  The dynamic engine
    carries spare sentinel slots in its push arenas so mutations ride the
    same extended-segment reduce without a reshape.
    """
    from repro.core.hybrid import add_identity, hybrid_spmv, hybrid_spmv_scan

    chaos.visit("kernel.hybrid", distributed=False)
    spec = program.edge_msg
    ident = add_identity(cfg.semiring)
    state, dopt = _dopt_strip(state)
    track = dopt is not None and "push_src" in arrs and "ell_kreal" in arrs
    q = state[spec.gather[0]].shape[0]
    n = cfg.num_vertices
    with obs.phase("bsp.layout"):
        vals = {k: state[k].astype(jnp.float32).reshape(q, -1)[:,
                                                               arrs["slot"]]
                for k in spec.gather}       # [Q, n] in hybrid id space
        # Per-partition scalar consts are replicated across partitions in
        # the single-device engines; the global compute reads partition
        # 0's copy (shaped [Q, 1] so they broadcast against the [Q, n]
        # values).
        consts = {c: state[c][:, :1].astype(jnp.float32)
                  for c in spec.consts}
        w_ident = None
        if spec.use_weight:
            w_ident = jnp.float32(0.0 if spec.weight_op == "add" else 1.0)
        x = spec.fn(vals, w_ident, step.astype(jnp.float32),
                    consts).astype(jnp.float32)          # [Q, n]

    widths = arrs.get("ell_width")      # sliced ELL (static engine)

    def pull(x):
        return hybrid_spmv(arrs["dense"], arrs["ell_col"], arrs["ell_val"],
                           x, semiring=cfg.semiring, k_dense=cfg.k_dense,
                           widths=widths, interpret=cfg.interpret)

    if "push_src" in arrs:
        def push_msgs(x):
            # Extended (n+1)-segment form: sentinel slots (src = dst = n,
            # e.g. the dynamic engine's spare push capacity) gather the
            # ⊕-identity sink and reduce into a discarded segment, so
            # padding is inert by construction.
            with obs.phase("bsp.gather"):
                x_ext = jnp.concatenate(
                    [x, jnp.full((q, 1), ident, x.dtype)], axis=1)
                msgs = x_ext[:, arrs["push_src"]]        # [Q, E]
                if "push_w" in arrs:
                    msgs = msgs + arrs["push_w"]
            with obs.phase("bsp.reduce"):
                offs = (jnp.arange(q, dtype=jnp.int32) * (n + 1))[:, None]
                y = jax.ops.segment_min(
                    msgs.ravel(), (arrs["push_dst"][None] + offs).ravel(),
                    num_segments=q * (n + 1))
                return y.reshape(q, n + 1)[:, :n], msgs

        # Per-query frontier density vs the fitted crossover, guarded by
        # the unvisited mass (still-⊕-identity vertices never early-exit
        # a pull scan), picks the direction — a perf choice only; both
        # directions are exact for min combines, and each query votes for
        # itself (satellite 1).
        with obs.phase("bsp.direction"):
            nf = jnp.float32(max(n, 1))
            density = jnp.sum((x != ident).astype(jnp.float32), axis=1) / nf
            unvisited = jnp.sum((vals[spec.gather[0]] == ident).astype(
                jnp.float32), axis=1) / nf
            want = _dopt_want(cfg.forced if track else None, density,
                              unvisited, cfg.pull_threshold)

        if track:
            e_dense = jnp.full((q,), cfg.e_dense, jnp.int32)

            def run_push(x):
                y, msgs = push_msgs(x)
                cnt = jnp.sum((msgs != ident).astype(jnp.int32), axis=1)
                return y, cnt, jnp.zeros((q,), jnp.int32)

            # Under the uniform licence a row already holding a value is
            # final — a sequential bottom-up skips it (zero scanned slots).
            skip = None
            if cfg.uniform:
                with obs.phase("bsp.direction"):
                    skip = vals[spec.gather[0]] != ident

            def run_pull(x):
                y, scanned = hybrid_spmv_scan(
                    arrs["dense"], arrs["ell_col"], arrs["ell_val"], x,
                    arrs["ell_kreal"], semiring=cfg.semiring,
                    k_dense=cfg.k_dense, early_exit=cfg.uniform,
                    skip=skip, widths=widths, interpret=cfg.interpret)
                return y, jnp.zeros((q,), jnp.int32), scanned + e_dense

            y, cnt_push, cnt_pull = _direction_select(
                want, run_push, run_pull, x)
            with obs.phase("bsp.direction"):
                dopt = _dopt_fold(dopt, want, cnt_push + cnt_pull)
        else:
            zero = jnp.zeros((q,), jnp.int32)
            y, _, _ = _direction_select(
                want,
                lambda x: (push_msgs(x)[0], zero, zero),
                lambda x: (pull(x), zero, zero), x)
    else:
        y = pull(x)

    with obs.phase("bsp.layout"):
        y_ext = jnp.concatenate([y, jnp.full((q, 1), ident, y.dtype)],
                                axis=1)
        acc = y_ext[:, arrs["hid"]]         # back to [Q, P, v_max] layout
    with obs.phase("bsp.apply"):
        new_state, finished = jax.vmap(program.apply_fn,
                                       in_axes=(0, 0, None))(state, acc, step)
        if dopt is not None:
            new_state = dict(new_state, **dopt)
        return new_state, all_finished(finished)


def _superstep_hybrid_dist(program: VertexProgram, shd, arrs: dict,
                           axis: str, interpret: Optional[bool],
                           pull_threshold: float,
                           all_finished: Callable[[Array], Array],
                           state: State, step: Array, *,
                           guard=None,
                           n_shards: Optional[int] = None,
                           forced: Optional[int] = None,
                           uniform: bool = False,
                           e_dense: int = 0) -> Tuple[State, Array]:
    """One BSP superstep of the *distributed* degree-split backend.

    Runs inside ``shard_map``: ``state`` leaves are the local
    ``[Q, pl, v_max]`` shard of the query batch, ``arrs`` the shard's slice
    of :class:`hybrid.ShardHybridData` (leading mesh axis of extent 1),
    shared across the batch.  The paper's cycle, per shard:

      1. evaluate the EdgeMessage once per local vertex (⊗-identity weight),
         then run the two-engine semiring SpMV over the shard's
         *intra-partition* edges (dense H×H MXU block + ELL remainder, with
         the push/pull frontier switch for min combines);
      2. reduce boundary messages into the ``o_max`` outbox slots at the
         source (``ops.outbox_reduce_op`` — the §3.4 aggregation, so the
         wire carries β_with_reduction·|E| values, never per-edge messages);
      3. exchange only the *used* (shard, peer) slot blocks via a compact
         ``all_to_all`` (Fig. 6's outbox→inbox copy over ICI); same-device
         peer slots short-circuit through a local gather/scatter;
      4. scatter inbox values into the local accumulator, combine with the
         SpMV result, apply + vote (global AND via psum).
    """
    from repro.core.hybrid import add_identity, hybrid_spmv, hybrid_spmv_scan
    from repro.kernels.ops import outbox_reduce_op

    chaos.visit("kernel.hybrid", distributed=True)
    spec = program.edge_msg
    ident = add_identity(shd.semiring)
    pl = shd.parts_per_shard
    v_max = shd.v_max
    slot = arrs["slot"][0]
    state, dopt = _dopt_strip(state)
    track = dopt is not None and "push_src" in arrs and "ell_kreal" in arrs
    q = state[spec.gather[0]].shape[0]
    with obs.phase("bsp.layout"):
        vals = {k: state[k].astype(jnp.float32).reshape(q, -1)[:, slot]
                for k in spec.gather}                   # [Q, n_max]
        consts = {c: state[c][:, :1].astype(jnp.float32)
                  for c in spec.consts}
        w_ident = None
        if spec.use_weight:
            w_ident = jnp.float32(0.0 if spec.weight_op == "add" else 1.0)
        x = spec.fn(vals, w_ident, step.astype(jnp.float32),
                    consts).astype(jnp.float32)         # [Q, n_max]
        n_vert = arrs["n_vert"][0]
        vmask = jnp.arange(shd.n_max, dtype=jnp.int32) < n_vert
        # pad hybrid ids never contribute
        x = jnp.where(vmask[None], x, ident)

    def pull(xv):
        return hybrid_spmv(arrs["dense"][0], arrs["ell_col"][0],
                           arrs["ell_val"][0], xv, semiring=shd.semiring,
                           k_dense=shd.k_dense, interpret=interpret)

    if "push_src" in arrs:
        def push_msgs(xv):
            with obs.phase("bsp.gather"):
                x_ext = jnp.concatenate(
                    [xv, jnp.full((q, 1), ident, xv.dtype)], axis=1)
                msgs = x_ext[:, arrs["push_src"][0]]    # [Q, ei]
                if "push_w" in arrs:
                    msgs = msgs + arrs["push_w"][0]
            with obs.phase("bsp.reduce"):
                offs = (jnp.arange(q, dtype=jnp.int32)
                        * (shd.n_max + 1))[:, None]
                y = jax.ops.segment_min(
                    msgs.ravel(),
                    (arrs["push_dst"][0][None] + offs).ravel(),
                    num_segments=q * (shd.n_max + 1))
                return y.reshape(q, shd.n_max + 1)[:, : shd.n_max], msgs

        # Per-(query, shard) frontier density vs this shard's fitted
        # crossover, guarded by the shard's unvisited mass, picks the
        # direction — each query votes for itself from the shard's own
        # frontier slice (a perf choice only; both directions are exact
        # for min combines).
        with obs.phase("bsp.direction"):
            thr = (arrs["pull_thr"][0][0, 0] if "pull_thr" in arrs
                   else pull_threshold)
            nf = jnp.maximum(n_vert.astype(jnp.float32), 1.0)
            density = jnp.sum((x != ident).astype(jnp.float32), axis=1) / nf
            unvisited = jnp.sum(jnp.logical_and(
                vals[spec.gather[0]] == ident,
                vmask[None]).astype(jnp.float32), axis=1) / nf
            want = _dopt_want(forced if track else None, density, unvisited,
                              thr)

        if track:
            with obs.phase("bsp.direction"):
                ed = (arrs["e_dense"][0][0] if "e_dense" in arrs
                      else jnp.int32(e_dense))
                e_dense_q = jnp.broadcast_to(ed.astype(jnp.int32), (q,))

            def run_push(xv):
                y, msgs = push_msgs(xv)
                cnt = jnp.sum((msgs != ident).astype(jnp.int32), axis=1)
                return y, cnt, jnp.zeros((q,), jnp.int32)

            # Uniform licence: rows already holding a value are final and
            # charge zero scanned slots (sequential bottom-up skips them).
            skip = None
            if uniform:
                with obs.phase("bsp.direction"):
                    skip = vals[spec.gather[0]] != ident

            def run_pull(xv):
                y, scanned = hybrid_spmv_scan(
                    arrs["dense"][0], arrs["ell_col"][0], arrs["ell_val"][0],
                    xv, arrs["ell_kreal"][0], semiring=shd.semiring,
                    k_dense=shd.k_dense, early_exit=uniform,
                    skip=skip, interpret=interpret)
                return y, jnp.zeros((q,), jnp.int32), scanned + e_dense_q

            y, cnt_push, cnt_pull = _direction_select(
                want, run_push, run_pull, x)
            with obs.phase("bsp.direction"):
                cnt = cnt_push + cnt_pull
                if shd.has_boundary:
                    # Boundary edges always run the push-style outbox
                    # reduction below, whichever way the intra step went —
                    # charge them in both directions.
                    x_ext = jnp.concatenate(
                        [x, jnp.full((q, 1), ident, x.dtype)], axis=1)
                    live = (x_ext[:, arrs["b_src"][0]] != ident)
                    live = jnp.logical_and(
                        live, (arrs["b_mask"][0] != 0)[None])
                    cnt = cnt + jnp.sum(live.astype(jnp.int32), axis=1)
                dopt = _dopt_fold(dopt, want, cnt)
        else:
            zero = jnp.zeros((q,), jnp.int32)
            y, _, _ = _direction_select(
                want,
                lambda xv: (push_msgs(xv)[0], zero, zero),
                lambda xv: (pull(xv), zero, zero), x)
    else:
        y = pull(x)

    seg_op = _SEGMENT_OP[program.combine]
    seg = shd.scatter_segments
    racc = None
    if shd.has_boundary:
        with obs.phase("bsp.reduce"):
            x_ext = jnp.concatenate([x, jnp.full((q, 1), ident, x.dtype)],
                                    axis=1)
            outbox = outbox_reduce_op(
                x_ext, arrs["b_src"][0], arrs["b_local"][0],
                arrs["b_mask"][0], arrs["b_ids"][0],
                arrs.get("b_weight", [None])[0],
                num_slots=shd.num_slots, combine=program.combine,
                weight_op=spec.weight_op if spec.use_weight else None,
                span=shd.b_span, block_e=shd.b_block,
                interpret=interpret)                    # [Q, num_slots]
        with obs.phase("bsp.exchange"):
            obox_ext = jnp.concatenate(
                [outbox, jnp.full((q, 1), ident, outbox.dtype)], axis=1)
            rvals, rids = [], []
            if shd.has_remote:
                send = obox_ext[:, arrs["send_idx"][0]]  # [Q, S, w]
                if (guard is not None and n_shards is not None
                        and n_shards > 1):
                    # Checksummed compact exchange: one reduction tag per
                    # destination shard, shipped over its own tiled
                    # all_to_all; the receiver re-tags its S/n_shards
                    # block per source.
                    blk = send.shape[1] // n_shards
                    tags = _payload_tag(
                        send.reshape(q, n_shards, blk, -1), (0, 2, 3))
                    send = jnp.where(guard.poison > 0, _flip_wire(send),
                                     send)
                    want = jax.lax.all_to_all(
                        tags.reshape(n_shards, 1), axis, split_axis=0,
                        concat_axis=0, tiled=True).reshape(n_shards)
                    recv = jax.lax.all_to_all(send, axis, split_axis=1,
                                              concat_axis=1, tiled=True)
                    got = _payload_tag(
                        recv.reshape(q, n_shards, blk, -1), (0, 2, 3))
                    guard.add(jnp.sum((got != want).astype(jnp.int32)))
                else:
                    recv = jax.lax.all_to_all(send, axis, split_axis=1,
                                              concat_axis=1, tiled=True)
                rvals.append(recv.reshape(q, -1))
                rids.append(arrs["recv_ids"][0].reshape(-1))
            if shd.has_local_slots:
                rvals.append(obox_ext[:, arrs["loc_idx"][0]])
                rids.append(arrs["loc_ids"][0])
            if rvals:
                ids = jnp.concatenate(rids)             # [L], shared over Q
                offs = (jnp.arange(q, dtype=jnp.int32)
                        * (seg + 1))[:, None]
                racc = seg_op(jnp.concatenate(rvals, axis=1).ravel(),
                              (ids[None] + offs).ravel(),
                              num_segments=q * (seg + 1))
                racc = racc.reshape(q, seg + 1)[:, :seg]
                racc = racc.reshape(q, pl, v_max + 1)[:, :, :v_max]

    with obs.phase("bsp.layout"):
        y_ext = jnp.concatenate([y, jnp.full((q, 1), ident, y.dtype)],
                                axis=1)
        acc = y_ext[:, arrs["hid"][0]]                  # [Q, pl, v_max]
    if racc is not None:
        with obs.phase("bsp.exchange"):
            acc = _COMBINE[program.combine](acc, racc)
    with obs.phase("bsp.apply"):
        new_state, finished = jax.vmap(program.apply_fn,
                                       in_axes=(0, 0, None))(state, acc, step)
        if dopt is not None:
            new_state = dict(new_state, **dopt)
        return new_state, all_finished(finished)


def _compute_reference(dims: _Dims, program: VertexProgram, edges: dict,
                       state: BatchedState, step: Array) -> Array:
    """Reference compute: gather → [Q, Pl, e_max] messages → scatter-reduce.

    ``edge_fn`` is written against unbatched [Pl, ...] state; vmap over the
    query axis runs it once per query against the *shared* edge arrays."""
    pl = edges["src"].shape[0]
    src, weight = edges["src"], edges.get("weight")
    with obs.phase("bsp.gather"):
        msgs = jax.vmap(
            lambda st: program.edge_fn(st, src, weight, step))(state)
    with obs.phase("bsp.reduce"):
        q = msgs.shape[0]
        offs = (jnp.arange(q * pl, dtype=jnp.int32)
                * dims.seg).reshape(q, pl, 1)
        ids = (edges["dst_ext"][None] + offs).ravel()
        acc = _SEGMENT_OP[program.combine](msgs.ravel(), ids,
                                           num_segments=q * pl * dims.seg)
        return acc.reshape(q, pl, dims.seg)


def _compute_fused(dims: _Dims, program: VertexProgram, edges: dict,
                   cfg: FusedConfig, state: BatchedState,
                   step: Array) -> Array:
    """Fused compute: one Pallas pass per (query, edge block), no
    [Q, Pl, e_max] HBM message array (kernels/fused_superstep.py)."""
    from repro.kernels.ops import fused_superstep_op

    # trace-time injection seam: a raise here aborts the compile, leaves no
    # jit-cache entry, and surfaces to the dispatching host as a kernel
    # fault — the degradation ladder's retry re-traces (and may re-fire)
    chaos.visit("kernel.fused", block_e=cfg.block_e)

    spec = program.edge_msg
    pl = edges["src"].shape[0]
    with obs.phase("bsp.gather"):
        vstate = jnp.stack([state[k].astype(jnp.float32)
                            for k in spec.gather], axis=2)  # [Q, Pl, K, v]
        q = vstate.shape[0]
        cols = [jnp.broadcast_to(step.astype(jnp.float32), (q, pl))]
        cols += [state[k].astype(jnp.float32) for k in spec.consts]
        scal = jnp.stack(cols, axis=2)                    # [Q, Pl, 1+consts]

    def msg_fn(vals, weight, scals):
        vals_d = dict(zip(spec.gather, vals))
        consts_d = dict(zip(spec.consts, scals[1:]))
        return spec.fn(vals_d, weight, scals[0], consts_d)

    weight = edges.get("weight_blk") if spec.use_weight else None
    with obs.phase("bsp.reduce"):
        return fused_superstep_op(
            msg_fn, vstate, weight, scal, edges["blk_src"],
            edges["blk_local"], edges["blk_mask"], edges["blk_ids"],
            edges["dst_ext"], num_segments=dims.seg,
            combine=program.combine, span=cfg.span, block_e=cfg.block_e,
            max_span=cfg.max_span, interpret=cfg.interpret)


def _superstep(dims: _Dims, program: VertexProgram, edges: dict,
               exchange: Callable[[Array], Array],
               all_finished: Callable[[Array], Array],
               fused_cfg: Optional[FusedConfig],
               state: BatchedState, step: Array,
               dyn: Optional[dict] = None,
               dopt_cfg: Optional[_DoptCfg] = None
               ) -> Tuple[BatchedState, Array]:
    """One BSP superstep of the whole query batch over the local shard.

    ``dyn`` (a ``DynamicGraph.payload`` dict, sharded alongside ``edges``)
    folds in-place mutations into the same superstep: tombstoned base edges
    are redirected to the segment sink (reference path) / masked out of
    their block (fused path), the masked **delta-slot tail** runs one extra
    reference-style reduction over the same extended segment space — so its
    boundary messages share the outbox slots and the exchange for free —
    and the live ``inbox_dst`` map carries slots assigned after partition
    time.  All shapes are mutation-independent; only values change.
    """
    combine = program.combine
    seg_op = _SEGMENT_OP[combine]
    pl = edges["src"].shape[0]  # local partition count
    state, dopt = _dopt_strip(state)
    spec = program.edge_msg
    track = (dopt is not None and dyn is None and spec is not None
             and dopt_cfg is not None and "t_col" in edges)

    if dyn is not None:
        edges = dict(edges)
        tomb = dyn["tomb"]
        edges["inbox_dst"] = dyn["inbox_dst"]
        with obs.phase("bsp.reduce"):
            edges["dst_ext"] = jnp.where(tomb, dims.v_max,
                                         edges["dst_ext"])
            if "blk_mask" in edges:
                pad = edges["blk_mask"].shape[1] - tomb.shape[1]
                alive = jnp.pad(jnp.logical_not(tomb), ((0, 0), (0, pad)))
                edges["blk_mask"] = edges["blk_mask"] * alive.astype(
                    edges["blk_mask"].dtype)

    # -- compute: per-edge messages, reduced over extended destinations -----
    def compute_push(state, step):
        if fused_cfg is not None and program.edge_msg is not None:
            return _compute_fused(dims, program, edges, fused_cfg, state,
                                  step)
        return _compute_reference(dims, program, edges, state, step)

    if track:
        from repro.kernels import ops as kops

        # Min combines only: both directions reduce the same per-destination
        # value multiset, so direction is a pure perf choice (bitwise).
        ident = jnp.float32(jnp.inf)
        v_max = dims.v_max
        q = state[spec.gather[0]].shape[0]
        with obs.phase("bsp.direction"):
            # Per-vertex messages; the push direction's per-edge messages
            # are gathers of exactly these values (the reference↔fused
            # bitwise parity already leans on edge_fn ≡ gather∘edge_msg.fn).
            vvals = {k: state[k].astype(jnp.float32) for k in spec.gather}
            vconsts = {c: state[c][:, :, None].astype(jnp.float32)
                       for c in spec.consts}
            w_ident = None
            if spec.use_weight:
                w_ident = jnp.float32(0.0 if spec.weight_op == "add"
                                      else 1.0)
            xv = spec.fn(vvals, w_ident, step.astype(jnp.float32),
                         vconsts).astype(jnp.float32)    # [Q, Pl, v_max]
            vmask = edges["t_vmask"]
            act = jnp.logical_and(xv != ident,
                                  vmask[None]).astype(jnp.float32)
            nreal = jnp.maximum(jnp.sum(vmask.astype(jnp.float32)), 1.0)
            density = jnp.sum(act, axis=(1, 2)) / nreal
            unvisited = jnp.sum(jnp.logical_and(
                vvals[spec.gather[0]] == ident,
                vmask[None]).astype(jnp.float32), axis=(1, 2)) / nreal
            deg = edges["t_deg"].astype(jnp.float32)
            bnd = edges["t_bnd"].astype(jnp.float32)
            # One direction serves every partition in this trace, so the
            # vote threshold is the edge-mass-weighted blend of the
            # per-partition fitted crossovers — exactly the shard's own fit
            # when shard_map hands this trace a single partition.
            emass = jnp.sum(deg, axis=1)
            thr = (jnp.sum(edges["t_thr"][:, 0] * emass)
                   / jnp.maximum(jnp.sum(emass), 1.0))
            want = _dopt_want(dopt_cfg.forced, density, unvisited, thr)
            # Push examines every out-edge of a live vertex; the boundary
            # leg always pushes (its messages ride the outbox/exchange
            # either way), so pull is charged the boundary out-edges on top
            # of its scans.
            cnt_push = jnp.sum(act * deg[None],
                               axis=(1, 2)).astype(jnp.int32)
            cnt_bnd = jnp.sum(act * bnd[None],
                              axis=(1, 2)).astype(jnp.int32)
            zero = jnp.zeros((q,), jnp.int32)

        def run_push(opd):
            st, step = opd
            return compute_push(st, step), cnt_push, zero

        def run_pull(opd):
            st, step = opd
            # Boundary-only reference pass: intra destinations redirect to
            # the segment sink, leaving outbox slots bitwise identical to
            # the full compute's — the local region comes from the
            # bottom-up kernel instead.
            e_bnd = dict(edges)
            with obs.phase("bsp.reduce"):
                e_bnd["dst_ext"] = jnp.where(edges["dst_ext"] < v_max,
                                             v_max, edges["dst_ext"])
            acc_b = _compute_reference(dims, program, e_bnd, st, step)
            with obs.phase("bsp.gather"):
                offs = (jnp.arange(pl, dtype=jnp.int32)
                        * (v_max + 1))[:, None, None]
                colf = (edges["t_col"] + offs).reshape(pl * v_max, -1)
                xf = jnp.concatenate(
                    [xv, jnp.full((q, pl, 1), ident, xv.dtype)],
                    axis=2).reshape(q, pl * (v_max + 1))
                valf = None
                if dopt_cfg.semiring == "min_plus":
                    valf = edges["t_val"].reshape(pl * v_max, -1)
            # Uniform licence: already-written rows are final — a
            # sequential bottom-up visits only unvisited rows, so they
            # charge zero scanned slots in the work model.
            skip = None
            if dopt_cfg.uniform:
                skip = (vvals[spec.gather[0]] != ident).reshape(
                    q, pl * v_max)
            y, scanned = kops.bottomup_scan_op(
                colf, valf, xf, edges["t_kreal"].reshape(pl * v_max),
                semiring=dopt_cfg.semiring, early_exit=dopt_cfg.uniform,
                skip=skip, interpret=dopt_cfg.interpret)
            with obs.phase("bsp.reduce"):
                acc = acc_b.at[:, :, :v_max].min(y.reshape(q, pl, v_max))
            cnt = jnp.sum(scanned, axis=1).astype(jnp.int32) + cnt_bnd
            return acc, zero, cnt

        acc, cp, cl = _direction_select(want, run_push, run_pull,
                                        (state, step))
        with obs.phase("bsp.direction"):
            dopt = _dopt_fold(dopt, want, cp + cl)
    else:
        acc = compute_push(state, step)

    if dyn is not None:
        # Delta-slot tail: inserted edges, reduced over the same segment
        # space (sink-pointing slots are unoccupied and vanish in the ⊕).
        d_edges = dict(src=dyn["d_src"], dst_ext=dyn["d_dst_ext"])
        if "d_weight" in dyn:
            d_edges["weight"] = dyn["d_weight"]
        d_dims = _Dims(dims.num_parts, dims.v_max,
                       dyn["d_src"].shape[1], dims.o_max)
        d_acc = _compute_reference(d_dims, program, d_edges, state, step)
        with obs.phase("bsp.reduce"):
            acc = _COMBINE[combine](acc, d_acc)

    with obs.phase("bsp.exchange"):
        q = acc.shape[0]
        local_acc = acc[:, :, : dims.v_max]
        outbox = acc[:, :, dims.v_max + 1:].reshape(q, pl, dims.num_parts,
                                                    dims.o_max)

        # -- communicate: outbox -> symmetric inbox (paper Fig. 6); the
        # wire ships Q slot blocks per pair — topology maps are never
        # duplicated ---------------------------------------------------------
        inbox = exchange(outbox)  # [Q, pl, P, o_max]

        # -- scatter: combine inbox messages into local vertex accumulator -
        offs = (jnp.arange(q * pl, dtype=jnp.int32)
                * (dims.v_max + 1)).reshape(q, pl, 1, 1)
        in_ids = edges["inbox_dst"][None] + offs
        racc = seg_op(inbox.ravel(), in_ids.ravel(),
                      num_segments=q * pl * (dims.v_max + 1))
        racc = racc.reshape(q, pl, dims.v_max + 1)[:, :, : dims.v_max]
        total = _COMBINE[combine](local_acc, racc)

    # -- apply + vote (per query) -------------------------------------------
    with obs.phase("bsp.apply"):
        new_state, finished = jax.vmap(program.apply_fn,
                                       in_axes=(0, 0, None))(state, total,
                                                             step)
        if dopt is not None:
            new_state = dict(new_state, **dopt)
        return new_state, all_finished(finished)


def _edges_dict(ea: EdgeArrays, blk: Optional[BlockMetadata] = None) -> dict:
    d = dict(src=jnp.asarray(ea.src), dst_ext=jnp.asarray(ea.dst_ext),
             inbox_dst=jnp.asarray(ea.inbox_dst))
    if ea.weight is not None:
        d["weight"] = jnp.asarray(ea.weight)
    if blk is not None:
        # Block metadata rides in the edges dict so it shards with the
        # partition axis under the distributed engine.
        d["blk_src"] = jnp.asarray(blk.src)
        d["blk_local"] = jnp.asarray(blk.local)
        d["blk_mask"] = jnp.asarray(blk.mask)
        d["blk_ids"] = jnp.asarray(blk.ids)
        if blk.weight is not None:
            d["weight_blk"] = jnp.asarray(blk.weight)
    return d


def _run_batched_loop(step_fn: Callable, max_steps: int,
                      state: BatchedState,
                      q: int) -> Tuple[BatchedState, Array]:
    """One ``lax.while_loop`` advancing all Q queries together.

    ``step_fn(state, step) -> (state, finished[Q])`` is any superstep
    closure; queries vote finish independently.  A converged query is
    masked out of the apply step — its state leaves freeze bitwise via a
    per-query ``where`` — while unfinished queries continue, so a batch
    reproduces each query's sequential trajectory exactly.  Returns the
    final state and per-query executed superstep counts ``steps[Q]``
    (identical to the sequential engine's ``steps`` for each query).
    """
    def freeze(fin, new, old):
        return jnp.where(fin.reshape(fin.shape + (1,) * (new.ndim - 1)),
                         old, new)

    def body(carry):
        st, step, fin, steps_q = carry
        new_st, vote = step_fn(st, step)
        with obs.phase("bsp.apply"):
            new_st = jax.tree.map(functools.partial(freeze, fin), new_st, st)
            steps_q = steps_q + jnp.logical_not(fin).astype(jnp.int32)
            return new_st, step + 1, jnp.logical_or(fin, vote), steps_q

    def cond(carry):
        _, step, fin, _ = carry
        return jnp.logical_and(~jnp.all(fin), step < max_steps)

    init = (state, jnp.int32(0), jnp.zeros((q,), jnp.bool_),
            jnp.zeros((q,), jnp.int32))
    state, _, _, steps_q = jax.lax.while_loop(cond, body, init)
    return state, steps_q


def _run_chunked_loop(step_fn: Callable, chunk: int, max_steps: int,
                      state: BatchedState, step0: Array, fin0: Array,
                      steps_q0: Array):
    """A bounded window of ``_run_batched_loop``: advance at most ``chunk``
    supersteps from a mid-run carry.

    Identical body (freeze-masked apply, per-query vote and step
    accounting); the cond additionally stops at ``step0 + chunk``.  Because
    ``step0`` is a **traced** operand, one compiled trace serves every
    window, and chaining windows end to end executes the exact same
    superstep sequence as the single resident loop — the carry that escapes
    to host between windows (state, step, finished votes, per-query step
    counters) is the checkpointable snapshot.  Returns the full carry.
    """
    def freeze(fin, new, old):
        return jnp.where(fin.reshape(fin.shape + (1,) * (new.ndim - 1)),
                         old, new)

    def body(carry):
        st, step, fin, steps_q = carry
        new_st, vote = step_fn(st, step)
        with obs.phase("bsp.apply"):
            new_st = jax.tree.map(functools.partial(freeze, fin), new_st, st)
            steps_q = steps_q + jnp.logical_not(fin).astype(jnp.int32)
            return new_st, step + 1, jnp.logical_or(fin, vote), steps_q

    def cond(carry):
        _, step, fin, _ = carry
        return jnp.logical_and(
            ~jnp.all(fin),
            jnp.logical_and(step < max_steps, step < step0 + chunk))

    return jax.lax.while_loop(cond, body, (state, step0, fin0, steps_q0))


# ---------------------------------------------------------------------------
# checksummed exchange (silent-corruption defense, docs/robustness.md)
# ---------------------------------------------------------------------------


def _payload_tag(x: Array, axes) -> Array:
    """Order-independent int32 reduction tag over ``axes`` of a payload.

    Bitcast-to-int32 then wrapping integer sum: deterministic under any
    reduction order (unlike float sums), and any single-element change moves
    the sum by a nonzero delta mod 2^32 — a one-bit wire flip always
    mismatches."""
    if x.dtype.itemsize == 4:
        words = jax.lax.bitcast_convert_type(x, jnp.int32)
    else:
        words = x.astype(jnp.int32)
    return jnp.sum(words, axis=axes, dtype=jnp.int32)


def _flip_wire(x: Array) -> Array:
    """Flip one mantissa bit of a payload's first element (the
    ``exchange.payload`` chaos drill's trace-level corruption)."""
    if x.dtype.itemsize != 4:
        return x
    flat = x.reshape(-1)
    words = jax.lax.bitcast_convert_type(flat, jnp.int32)
    words = words.at[0].set(words[0] ^ jnp.int32(1 << 20))
    return jax.lax.bitcast_convert_type(words, x.dtype).reshape(x.shape)


def _flip_state_bit(state: BatchedState, bit: int = 20) -> BatchedState:
    """Host-side single-bit corruption of every float32 state leaf (the
    ``state.corrupt`` chaos site).  Runs between compiled windows, so it
    models a DRAM/transfer bit-flip without perturbing the jit cache."""
    def flip(leaf):
        arr = np.array(leaf)
        if arr.dtype != np.float32 or arr.size == 0:
            return leaf
        arr.reshape(-1).view(np.int32)[0] ^= np.int32(1 << bit)
        return jnp.asarray(arr)
    return jax.tree.map(flip, state)


class _ExchangeGuard:
    """Per-engine box threading exchange-checksum state through a trace.

    Stored as an engine attribute so the jitted chunk methods (whose
    ``self`` is a static argument) see one stable closure identity;
    ``arm``/``reset``/``add``/``read`` are trace-time operations — the
    armed ``poison`` operand and the accumulated mismatch count are traced
    values referenced positionally by the compiled window, so cache hits
    behave identically to the first trace."""

    def __init__(self):
        self.poison = jnp.float32(0.0)
        self._bad = jnp.int32(0)

    def arm(self, poison: Array) -> None:
        self.poison = poison

    def reset(self) -> None:
        self._bad = jnp.int32(0)

    def add(self, n: Array) -> None:
        self._bad = self._bad + jnp.asarray(n, jnp.int32)

    def read(self) -> Array:
        return self._bad


def _checked_exchange(guard: _ExchangeGuard) -> Callable[[Array], Array]:
    """Single-device exchange with per-(partition, peer) reduction tags.

    Send-side tags are computed on the outbox slot blocks *before* the wire
    (where the ``exchange.payload`` drill corrupts under the armed poison
    operand); the inbox side re-derives them and any mismatch lands in the
    guard — the host converts a nonzero window count into an
    ``ExchangeCorruption`` and replays the window."""
    def exchange(outbox: Array) -> Array:
        send_tags = _payload_tag(outbox, (0, 3))            # [pl, P]
        wire = jnp.where(guard.poison > 0, _flip_wire(outbox), outbox)
        inbox = wire.transpose(0, 2, 1, 3)                  # [Q, P, pl, o]
        recv_tags = _payload_tag(inbox, (0, 3))             # [P, pl]
        guard.add(jnp.sum((recv_tags != send_tags.T).astype(jnp.int32)))
        return inbox
    return exchange


def _run_chunked_loop_guarded(step_fn: Callable, guard: _ExchangeGuard,
                              chunk: int, max_steps: int,
                              state: BatchedState, step0: Array, fin0: Array,
                              steps_q0: Array):
    """:func:`_run_chunked_loop` with the exchange guard in the carry.

    Identical superstep semantics (the extra carry element never feeds back
    into the state); per superstep the guard is reset, the step function's
    checked exchanges accumulate mismatches into it, and the count joins
    the loop carry — read *inside* the body trace, so no tracer leaks.
    Returns ``(state, step, fin, steps_q, bad)``."""
    def freeze(fin, new, old):
        return jnp.where(fin.reshape(fin.shape + (1,) * (new.ndim - 1)),
                         old, new)

    def body(carry):
        st, step, fin, steps_q, bad = carry
        guard.reset()
        new_st, vote = step_fn(st, step)
        with obs.phase("bsp.apply"):
            new_st = jax.tree.map(functools.partial(freeze, fin), new_st, st)
            steps_q = steps_q + jnp.logical_not(fin).astype(jnp.int32)
            return (new_st, step + 1, jnp.logical_or(fin, vote), steps_q,
                    bad + guard.read())

    def cond(carry):
        _, step, fin, _, _ = carry
        return jnp.logical_and(
            ~jnp.all(fin),
            jnp.logical_and(step < max_steps, step < step0 + chunk))

    return jax.lax.while_loop(
        cond, body, (state, step0, fin0, steps_q0, jnp.int32(0)))


@jax.jit
def _slot_swap(state: BatchedState, new_rows: BatchedState, admit: Array,
               fin: Array, steps_q: Array):
    """Static-shape slot refill for continuous batching.

    ``admit`` is a ``[Q]`` bool mask of slots taking a new tenant: their
    state leaves are replaced wholesale by ``new_rows``' (a full-Q pytree
    whose non-admitted rows are ignored), their finished votes cleared, and
    their superstep counters zeroed — everything else passes through
    **bitwise** unchanged.  Q is static and the carry shapes never change,
    so one compiled trace serves every refill of a serving session; the
    same trace serves ``DistributedBSPEngine`` (the query axis is
    replicated — a per-slot swap needs no communication, and the next
    chunk window re-shards the carry on entry).
    """
    def swap(new, old):
        return jnp.where(admit.reshape(admit.shape + (1,) * (old.ndim - 1)),
                         new, old)

    state = jax.tree.map(swap, new_rows, state)
    fin = jnp.where(admit, jnp.bool_(False), fin)
    steps_q = jnp.where(admit, jnp.int32(0), steps_q)
    return state, fin, steps_q


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _run_dyn_jit(dims: _Dims, program: VertexProgram,
                 fused_cfg: Optional[FusedConfig], max_steps: int,
                 fixed_steps: Optional[int], edges: dict, dyn: dict,
                 state: BatchedState):
    """Dynamic-graph batched runner (reference/fused backends).

    Unlike the static ``BSPEngine.run_batched`` — whose closed-over edge
    arrays become compiled constants — every array here (base edges AND the
    mutation payload) is a **traced argument**: mutation batches between
    runs reuse one trace (shapes never change), and a compaction can never
    be served stale values from the jit cache (a shape change retraces, a
    shape-preserving rebuild just passes new operands).
    """
    step_fn = functools.partial(_superstep, dims, program, edges,
                                BSPEngine._exchange,
                                BSPEngine._all_finished, fused_cfg, dyn=dyn)
    if fixed_steps is not None:
        def body(i, st):
            st, _ = step_fn(st, i)
            return st
        return jax.lax.fori_loop(0, fixed_steps, body, state)
    return _run_batched_loop(step_fn, max_steps, state, num_queries(state))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _run_dyn_chunk_jit(dims: _Dims, program: VertexProgram,
                       fused_cfg: Optional[FusedConfig], max_steps: int,
                       chunk: int, edges: dict, dyn: dict,
                       state: BatchedState, step: Array, fin: Array,
                       steps_q: Array):
    """Chunked window of ``_run_dyn_jit`` (same traced-operand contract:
    mutation batches and engine rebuilds after a restart reuse one trace)."""
    step_fn = functools.partial(_superstep, dims, program, edges,
                                BSPEngine._exchange,
                                BSPEngine._all_finished, fused_cfg, dyn=dyn)
    return _run_chunked_loop(step_fn, chunk, max_steps, state, step, fin,
                             steps_q)


def _vote_never(apply_fn):
    def wrapped(state, acc, step):
        new_state, _ = apply_fn(state, acc, step)
        return new_state, jnp.bool_(False)
    return wrapped


@functools.lru_cache(maxsize=None)
def _fixed_step_program(program: VertexProgram,
                        num_steps: int) -> VertexProgram:
    """Fixed-iteration restatement of ``program``: never votes finish, so
    the while_loop path runs exactly ``num_steps`` supersteps — how the
    *distributed dynamic* engine serves ``run_fixed_batched`` through the
    same sharded machinery as ``run_batched``.  Memoized so repeated calls
    reuse one program identity (the jit caches key on it)."""
    return dataclasses.replace(program, max_steps=num_steps,
                               apply_fn=_vote_never(program.apply_fn))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _run_dyn_hybrid_jit(program: VertexProgram, cfg: _HybridCfg,
                        max_steps: int, fixed_steps: Optional[int],
                        arrs: dict, state: BatchedState):
    """Dynamic-graph batched runner, hybrid degree-split backend: the
    dense block / ELL arrays arrive as traced arguments so in-place
    mutation writes (and post-compaction rebuilds) never hit a stale
    compiled constant."""
    step_fn = functools.partial(_superstep_hybrid, program, cfg, arrs,
                                BSPEngine._all_finished)
    if fixed_steps is not None:
        def body(i, st):
            st, _ = step_fn(st, i)
            return st
        return jax.lax.fori_loop(0, fixed_steps, body, state)
    return _run_batched_loop(step_fn, max_steps, state, num_queries(state))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _run_dyn_hybrid_chunk_jit(program: VertexProgram, cfg: _HybridCfg,
                              max_steps: int, chunk: int, arrs: dict,
                              state: BatchedState, step: Array, fin: Array,
                              steps_q: Array):
    """Chunked window of ``_run_dyn_hybrid_jit``."""
    step_fn = functools.partial(_superstep_hybrid, program, cfg, arrs,
                                BSPEngine._all_finished)
    return _run_chunked_loop(step_fn, chunk, max_steps, state, step, fin,
                             steps_q)


# ---------------------------------------------------------------------------
# Tiered (out-of-core) execution: host-resident cold partitions streamed
# through the superstep in double-buffered clean-cut windows (docs/memory.md)
# ---------------------------------------------------------------------------

def _cache_entries_of(fn) -> int:
    getter = getattr(fn, "_cache_size", None)
    return int(getter()) if getter is not None else 0


def _ident_of(combine: str):
    return jnp.float32(jnp.inf) if combine == MIN else jnp.float32(0.0)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _tiered_hot_jit(dims: _Dims, program: VertexProgram,
                    fused_cfg: Optional[FusedConfig], hot_idx, edges_hot,
                    dyn_hot, state: BatchedState, step: Array) -> Array:
    """Identity-initialized [Q, P, seg] accumulator with the hot (resident)
    partitions' compute folded in.

    Per-(query, partition) segment offsets make the big resident reduce
    row-independent, so running the same compute on the hot row *subset* and
    scattering into the full accumulator reproduces those rows bitwise; cold
    rows stay at the reduction identity until their windows stream through.
    ``dyn_hot`` carries the hot rows' tombstone/delta overlay (the same
    folding ``_superstep`` applies, sliced to the resident rows).
    """
    q = num_queries(state)
    acc = jnp.full((q, dims.num_parts, dims.seg),
                   _ident_of(program.combine), jnp.float32)
    if edges_hot is None:
        return acc
    state_h = jax.tree.map(lambda x: x[:, hot_idx], state)
    edges = edges_hot
    if dyn_hot is not None:
        edges = dict(edges)
        tomb = dyn_hot["tomb"]
        edges["dst_ext"] = jnp.where(tomb, dims.v_max, edges["dst_ext"])
        if "blk_mask" in edges:
            pad = edges["blk_mask"].shape[1] - tomb.shape[1]
            alive = jnp.pad(jnp.logical_not(tomb), ((0, 0), (0, pad)))
            edges["blk_mask"] = edges["blk_mask"] * alive.astype(
                edges["blk_mask"].dtype)
    if fused_cfg is not None and program.edge_msg is not None:
        acc_h = _compute_fused(dims, program, edges, fused_cfg, state_h, step)
    else:
        acc_h = _compute_reference(dims, program, edges, state_h, step)
    if dyn_hot is not None:
        d_edges = dict(src=dyn_hot["d_src"], dst_ext=dyn_hot["d_dst_ext"])
        if "d_weight" in dyn_hot:
            d_edges["weight"] = dyn_hot["d_weight"]
        d_acc = _compute_reference(dims, program, d_edges, state_h, step)
        acc_h = _COMBINE[program.combine](acc_h, d_acc)
    return acc.at[:, hot_idx].set(acc_h)


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(4,))
def _tiered_window_jit(dims: _Dims, program: VertexProgram,
                       fused_cfg: Optional[FusedConfig], p: Array,
                       acc: Array, win: dict, state: BatchedState,
                       step: Array) -> Array:
    """Fold one streamed cold-partition window into accumulator row ``p``.

    ``p`` is a *traced* scalar and every window of a schedule has the same
    fixed shapes (short windows arrive sink-padded), so one compiled trace
    serves the whole stream — the steady state never retraces.  ``acc`` is
    donated: the in-flight double buffer is the only extra device memory.
    Clean-cut windows mean each segment's real edges live in exactly one
    window; every other window contributes the reduction identity, which
    the cross-window combine absorbs bitwise.
    """
    state_p = jax.tree.map(
        lambda x: jax.lax.dynamic_slice_in_dim(x, p, 1, axis=1), state)
    edges = {k: v[None] for k, v in win.items() if k != "tomb"}
    if "tomb" in win:
        tomb = win["tomb"][None]
        edges["dst_ext"] = jnp.where(tomb, dims.v_max, edges["dst_ext"])
        if "blk_mask" in edges:
            edges["blk_mask"] = edges["blk_mask"] * jnp.logical_not(
                tomb).astype(edges["blk_mask"].dtype)
    if fused_cfg is not None and program.edge_msg is not None:
        acc_w = _compute_fused(dims, program, edges, fused_cfg, state_p, step)
    else:
        acc_w = _compute_reference(dims, program, edges, state_p, step)
    row = jax.lax.dynamic_slice_in_dim(acc, p, 1, axis=1)
    row = _COMBINE[program.combine](row, acc_w)
    return jax.lax.dynamic_update_slice_in_dim(acc, row, p, axis=1)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def _tiered_apply_jit(dims: _Dims, program: VertexProgram, acc: Array,
                      inbox_dst: Array, state: BatchedState, step: Array,
                      fin: Array, steps_q: Array):
    """Exchange + scatter + apply on a fully-assembled accumulator: the tail
    of ``_superstep`` plus ``_run_batched_loop``'s freeze/vote body, so one
    host-driven tiered superstep is carry-for-carry identical to one
    resident loop iteration."""
    combine = program.combine
    seg_op = _SEGMENT_OP[combine]
    q, pl = acc.shape[0], dims.num_parts
    local_acc = acc[:, :, : dims.v_max]
    outbox = acc[:, :, dims.v_max + 1:].reshape(q, pl, dims.num_parts,
                                                dims.o_max)
    inbox = BSPEngine._exchange(outbox)
    offs = (jnp.arange(q * pl, dtype=jnp.int32)
            * (dims.v_max + 1)).reshape(q, pl, 1, 1)
    in_ids = inbox_dst[None] + offs
    racc = seg_op(inbox.ravel(), in_ids.ravel(),
                  num_segments=q * pl * (dims.v_max + 1))
    racc = racc.reshape(q, pl, dims.v_max + 1)[:, :, : dims.v_max]
    total = _COMBINE[combine](local_acc, racc)
    new_state, vote = jax.vmap(program.apply_fn,
                               in_axes=(0, 0, None))(state, total, step)

    def freeze(new, old):
        return jnp.where(fin.reshape(fin.shape + (1,) * (new.ndim - 1)),
                         old, new)

    new_state = jax.tree.map(freeze, new_state, state)
    steps_q = steps_q + jnp.logical_not(fin).astype(jnp.int32)
    fin = jnp.logical_or(fin, vote)
    return new_state, fin, steps_q


@functools.partial(jax.jit, static_argnums=(0, 1))
def _tiered_hyb_hot_jit(program: VertexProgram, cfg: _HybridCfg, slot,
                        col_hot, val_hot, rows_hot, state: BatchedState,
                        step: Array):
    """Hybrid flavor: message vector + identity-initialized per-row ELL
    accumulator with the resident (hot-partition) rows' reductions
    scattered in.  A whole ELL row is atomic — its kmax-entry reduce runs
    wherever the row lives — so row-level tiering needs no clean-cut
    analysis; the dense MXU block always stays resident."""
    from repro.core.hybrid import add_identity
    from repro.kernels.ops import ell_spmv_op

    spec = program.edge_msg
    ident = add_identity(cfg.semiring)
    q = state[spec.gather[0]].shape[0]
    vals = {k: state[k].astype(jnp.float32).reshape(q, -1)[:, slot]
            for k in spec.gather}
    consts = {c: state[c][:, :1].astype(jnp.float32) for c in spec.consts}
    w_ident = None
    if spec.use_weight:
        w_ident = jnp.float32(0.0 if spec.weight_op == "add" else 1.0)
    x = spec.fn(vals, w_ident, step.astype(jnp.float32),
                consts).astype(jnp.float32)
    xs = jnp.concatenate([x, jnp.full((q, 1), ident, x.dtype)], axis=1)
    y = jnp.full((q, cfg.num_vertices + 1), ident, jnp.float32)
    if col_hot is not None:
        y_hot = ell_spmv_op(col_hot, val_hot, xs, semiring=cfg.semiring,
                            interpret=cfg.interpret)
        y = y.at[:, rows_hot].set(y_hot)
    return xs, y


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _tiered_hyb_win_jit(cfg: _HybridCfg, y: Array, col_w, val_w, rows_w,
                        xs: Array) -> Array:
    """One streamed ELL row window: reduce the window's rows, scatter-set
    them into the per-row accumulator (pad rows land on the sink column)."""
    from repro.kernels.ops import ell_spmv_op

    y_w = ell_spmv_op(col_w, val_w, xs, semiring=cfg.semiring,
                      interpret=cfg.interpret)
    return y.at[:, rows_w].set(y_w)


def _make_tiered_hyb_acc(cfg: _HybridCfg, dense, hid):
    """Build the dense-block-combine + layout-gather jit for one tiered
    hybrid binding: mirrors ``hybrid_spmv``'s ELL-then-dense order and
    returns the [Q, P, v_max] accumulator.

    ``dense``/``hid`` are deliberately *closed over as numpy* so they enter
    the trace as constants, exactly as the resident ``_superstep_hybrid``
    trace sees them: a constant adjacency operand lets XLA pick the same
    gemm layout (and hence the same accumulation order) in both
    compilations — passed as device parameters instead, the dot rounds
    1 ulp differently and streamed-vs-resident bitwise parity breaks."""
    from repro.core.hybrid import add_identity
    from repro.kernels import ops as kops

    ident = add_identity(cfg.semiring)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def acc_fn(y: Array, xs: Array) -> Array:
        q = y.shape[0]
        yv = y[:, : cfg.num_vertices]
        if cfg.k_dense:
            # Barriers matched with ``hybrid_spmv``'s dense stage: both
            # paths compile the dot as the same isolated subgraph.
            x = jax.lax.optimization_barrier(xs[:, : cfg.k_dense])
            if cfg.semiring == "plus_times":
                yh = jax.lax.optimization_barrier(
                    kops.dense_spmv_op(x, dense, interpret=cfg.interpret))
                yv = yv.at[:, : cfg.k_dense].add(yh)
            else:
                yh = jax.lax.optimization_barrier(
                    kops.dense_spmv_minplus_op(x, dense,
                                               interpret=cfg.interpret))
                yv = yv.at[:, : cfg.k_dense].min(yh)
        y_ext = jnp.concatenate([yv, jnp.full((q, 1), ident, yv.dtype)],
                                axis=1)
        return y_ext[:, hid]

    return acc_fn


@functools.partial(jax.jit, static_argnums=(0,))
def _tiered_hyb_apply_jit(program: VertexProgram, acc: Array,
                          state: BatchedState, step: Array, fin: Array,
                          steps_q: Array):
    """Apply + the batched loop's freeze/vote body.

    ``acc`` arrives as a jit *parameter* on purpose: when the accumulator
    assembly shares a graph with ``apply_fn``, XLA's FMA-contraction choice
    for expressions like ``delta + damping * acc`` can differ from the
    resident compilation's by 1 ulp — the parameter boundary pins the
    rounding the resident path exhibits."""
    new_state, vote = jax.vmap(program.apply_fn,
                               in_axes=(0, 0, None))(state, acc, step)

    def freeze(new, old):
        return jnp.where(fin.reshape(fin.shape + (1,) * (new.ndim - 1)),
                         old, new)

    new_state = jax.tree.map(freeze, new_state, state)
    steps_q = steps_q + jnp.logical_not(fin).astype(jnp.int32)
    fin = jnp.logical_or(fin, vote)
    return new_state, fin, steps_q


_TIERED_JITS = (_tiered_hot_jit, _tiered_window_jit, _tiered_apply_jit,
                _tiered_hyb_hot_jit, _tiered_hyb_win_jit,
                _tiered_hyb_apply_jit)


def tiered_cache_entries() -> int:
    """Total compile-cache entries across the tiered-path jits (the
    zero-steady-state-retrace gates diff this across supersteps)."""
    return sum(_cache_entries_of(f) for f in _TIERED_JITS)


# ---------------------------------------------------------------------------
# one-shot DeprecationWarnings for the pre-execute() aliases
# ---------------------------------------------------------------------------

REFERENCE = "reference"
FUSED = "fused"
HYBRID = "hybrid"
BACKENDS = (REFERENCE, FUSED, HYBRID)


class BSPEngine:
    """Single-device engine: all P partitions stacked on axis 0.

    Three selectable execution backends for the compute phase:

    - ``backend="reference"`` — gather → [Pl, e_max] messages →
      segment-reduce (always available; the correctness oracle).
    - ``backend="fused"`` — the fused Pallas superstep kernel for programs
      that carry an :class:`EdgeMessage` form; falls back to reference
      whenever a direction's measured block span exceeds ``max_span``
      (degree-skewed / gappy destination data — see
      ``BlockMetadata.span_histogram``).  ``fused=True`` is the back-compat
      spelling.
    - ``backend="hybrid"`` — the degree-split two-engine step (dense H×H MXU
      block + ELL remainder, core/hybrid.py) run as a whole-graph semiring
      SpMV; ``hybrid_k_dense=None`` lets the performance model pick the
      split (argmin predicted makespan — the paper's Eq. 4 role), and for
      min combines a frontier-density ``pull_threshold`` switches push/pull
      direction per superstep.  Requires ``pg.source``; programs without an
      eligible EdgeMessage run the reference path.
    """

    def __init__(self, pg, *, backend: Optional[str] = None,
                 fused: bool = False, block_e: int = 1024,
                 max_span: int = 4096,
                 interpret: Optional[bool] = None,
                 hybrid_k_dense: Optional[int] = None,
                 pull_threshold: Optional[float] = None,
                 direction: str = "auto",
                 direction_switch: bool = True,
                 dynamic_ell_spare: int = 8,
                 tiered=None, win_blocks: int = 8):
        from repro.core.dynamic import DynamicGraph

        if backend is None:
            backend = FUSED if fused else REFERENCE
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; pick one of "
                             f"{BACKENDS}")
        if direction not in ("auto", "push", "pull"):
            raise ValueError(f"direction must be 'auto', 'push' or 'pull', "
                             f"got {direction!r}")
        self.backend = backend
        self.fused = backend == FUSED
        self.interpret = interpret
        self._block_e = block_e
        self._max_span = max_span
        self._hybrid_k_dense = hybrid_k_dense
        # None → fit the push/pull crossover from the perf model
        # (perf_model.fit_pull_threshold, per backend / per shard); a float
        # forces that density threshold everywhere.
        self._pull_threshold_req = pull_threshold
        self._pull_threshold = (0.05 if pull_threshold is None
                                else pull_threshold)
        self.direction = direction
        self._dopt_forced = {"auto": None, "push": _DIR_PUSH,
                             "pull": _DIR_PULL}[direction]
        self._direction_switch = direction_switch
        # Per-query direction decisions of the last execute() on an
        # eligible min-combine program: {"direction" [Q, P] (-1 = never
        # decided), "edges_examined" [Q], "switches" [Q]}.
        self.last_direction_stats: Optional[dict] = None
        self._dyn_ell_spare = dynamic_ell_spare
        # Out-of-core tiering: ``tiered`` is an HBM byte budget (int) or a
        # prebuilt partition.TierPlan; None keeps everything resident.
        self._tiered_req = tiered
        self._win_blocks = win_blocks
        self.tier_plan = None
        # One guard per engine: jitted chunk windows arm it with the traced
        # poison operand and accumulate exchange-checksum mismatches.
        self._guard = _ExchangeGuard()

        # Dynamic graphs hand the engine a mutable layout: the engine reads
        # the mutation payload as traced jit arguments each run (never as
        # compiled constants) and rebinds itself after a compaction.
        self.dg: Optional[DynamicGraph] = None
        self.dynamic_rebinds = 0
        # dynamic-hybrid split rebuilds (spare-ELL overflow / batch log no
        # longer reaching the cursor): legitimate shape-changing recompiles
        # the retrace gates must discount, like compaction rebinds
        self.hybrid_dyn_rebuilds = 0
        if isinstance(pg, DynamicGraph):
            self.dg = pg
            self._dyn_version = pg.version
            pg = pg.pg
        if (self._tiered_req is not None and self.backend == HYBRID
                and self.dg is not None):
            raise ValueError(
                "tiered= with backend='hybrid' does not support dynamic "
                "graphs: delta slots stream with their base edge blocks, "
                "which the row-tiered ELL split has no blocks for; use "
                "backend='reference' or 'fused' for tiered dynamic runs")
        self._bind(pg)
        if self.dg is not None:
            # Instance-level dispatch: the class attributes stay the jitted
            # static-path methods (their compile-cache introspection is part
            # of the serving contract); a dynamic engine shadows them.
            self._converge = self._run_batched_dyn
            self._fixed = self._run_fixed_batched_dyn
        if self.tier_plan is not None:
            # Tiered shadows go on *after* the dynamic ones so tiered
            # dispatch wins; the tiered loop folds the dynamic payload in
            # itself (hot rows sliced on device, cold tombstones/deltas
            # streamed with their partitions' windows).
            self._converge = self._run_batched_tiered
            self._fixed = self._run_fixed_batched_tiered

    @property
    def pg(self) -> PartitionedGraph:
        """The current partitioned layout.  On a dynamic engine this first
        syncs with the DynamicGraph (rebinds after compaction — and, on the
        distributed hybrid, folds pending mutations), so state constructed
        from ``engine.pg`` always matches the layout the next run uses."""
        if self.dg is not None:
            self._sync_dynamic()
        return self._pg

    def _bind(self, pg: PartitionedGraph) -> None:
        """Derive every pg-shaped structure (edge dicts, block metadata,
        hybrid plan/caches).  Construction and post-compaction rebinds both
        land here."""
        self._pg = pg
        block_e = self._block_e
        self.dims = _Dims(pg.num_parts, pg.v_max, pg.fwd.e_max, pg.fwd.o_max)
        self._fwd_blk = self._rev_blk = None
        if self.fused:
            self._fwd_blk = build_block_metadata(pg.fwd, block_e=block_e)
            if pg.rev is not None:
                self._rev_blk = build_block_metadata(pg.rev, block_e=block_e)
        self._fwd = _edges_dict(pg.fwd, self._fwd_blk)
        self._rev = (_edges_dict(pg.rev, self._rev_blk)
                     if pg.rev is not None else None)

        def _cfg(blk):
            if blk is None:
                return None
            return FusedConfig(span=blk.span, block_e=blk.block_e,
                               max_span=self._max_span,
                               interpret=self.interpret)

        self._fwd_cfg = _cfg(self._fwd_blk)
        self._rev_cfg = _cfg(self._rev_blk)
        self.out_deg = jnp.asarray(pg.out_deg)
        self.vertex_mask = jnp.asarray(pg.vertex_mask)

        self._hybrid_cache: dict = {}
        self._hybrid_dyn_cache: dict = {}
        self._chunk_jits: dict = {}
        self._hybrid_plan: Optional[dict] = None
        if self.backend == HYBRID:
            if pg.source is None:
                raise ValueError(
                    "hybrid backend needs PartitionedGraph.source; "
                    "re-partition with core.partition.partition()")
            self._hybrid_plan = self._plan_hybrid(self._hybrid_k_dense,
                                                  block_e)
        self._bind_tiered(pg)

    def _bind_tiered(self, pg: PartitionedGraph) -> None:
        """Out-of-core residency: split partitions across the HBM/host tiers
        and stage the cold ones as host window arenas.

        Hot partitions' edge (and block) arrays go on device once, exactly
        like the resident dicts; each cold partition's edges become a list
        of clean-cut windows — fixed-shape numpy dicts the run loop
        ``jax.device_put``s through a double buffer.  Window padding is the
        per-row segment sink (reference) / masked-out blocks with a sink
        base (fused), so a short window reduces to exactly its real edges.
        The hybrid backend tiers at ELL-row granularity instead and keeps
        its normal binding (built lazily per program in
        ``_hybrid_tiered_for``)."""
        from repro.core.partition import TierPlan, build_tier_plan

        self.tier_plan = None
        self._hyb_tier_cache: dict = {}
        if self._tiered_req is None:
            return
        if isinstance(self._tiered_req, TierPlan):
            self.tier_plan = self._tiered_req
        else:
            self.tier_plan = build_tier_plan(
                pg, int(self._tiered_req), block_e=self._block_e,
                win_blocks=self._win_blocks,
                fused=(self.backend != REFERENCE), dynamic=self.dg)
        plan = self.tier_plan
        hot = np.asarray(plan.hot, dtype=np.int64)
        self._tier_hot_idx = jnp.asarray(hot.astype(np.int32))
        self._tier_dev: dict = {}
        self._tier_arena: dict = {}
        self._tier_dims: dict = {}
        self._tier_inbox: dict = {}
        for use_rev, ea, blk, sched in (
                (False, pg.fwd, self._fwd_blk, plan.fwd),
                (True, pg.rev, self._rev_blk, plan.rev)):
            if ea is None or sched is None:
                continue
            dims = _Dims(pg.num_parts, pg.v_max, ea.e_max, ea.o_max)
            self._tier_dims[use_rev] = dims
            self._tier_inbox[use_rev] = jnp.asarray(ea.inbox_dst)

            d = None
            if len(hot):
                d = dict(src=jnp.asarray(ea.src[hot]),
                         dst_ext=jnp.asarray(ea.dst_ext[hot]))
                if ea.weight is not None:
                    d["weight"] = jnp.asarray(ea.weight[hot])
                if blk is not None:
                    d["blk_src"] = jnp.asarray(blk.src[hot])
                    d["blk_local"] = jnp.asarray(blk.local[hot])
                    d["blk_mask"] = jnp.asarray(blk.mask[hot])
                    d["blk_ids"] = jnp.asarray(blk.ids[hot])
                    if blk.weight is not None:
                        d["weight_blk"] = jnp.asarray(blk.weight[hot])
            self._tier_dev[use_rev] = d

            win_e = sched.win_e
            arena = []
            for p, st, cnt in zip(sched.part, sched.start, sched.count):
                p, st, cnt = int(p), int(st), int(cnt)
                src = np.zeros(win_e, np.int32)
                src[:cnt] = ea.src[p, st:st + cnt]
                dst = np.full(win_e, pg.v_max, np.int32)
                dst[:cnt] = ea.dst_ext[p, st:st + cnt]
                w = dict(src=src, dst_ext=dst)
                if ea.weight is not None:
                    wt = np.zeros(win_e, np.float32)
                    wt[:cnt] = ea.weight[p, st:st + cnt]
                    w["weight"] = wt
                if blk is not None:
                    # Slices past this window's real blocks would alias the
                    # *next* window's real edges (the flat block arrays are
                    # contiguous per partition) — pad with masked-out zeros
                    # and sink bases instead of slicing blindly.
                    for key, arr in (("blk_src", blk.src),
                                     ("blk_local", blk.local),
                                     ("blk_mask", blk.mask)):
                        a = np.zeros(win_e, np.int32)
                        a[:cnt] = arr[p, st:st + cnt]
                        w[key] = a
                    nb = -(-cnt // sched.block_e)
                    b0 = st // sched.block_e
                    ids = np.full((sched.win_blocks, blk.span), -1,
                                  np.int32)
                    ids[:nb] = blk.ids[p, b0:b0 + nb]
                    w["blk_ids"] = ids
                    if blk.weight is not None:
                        a = np.zeros(win_e, np.float32)
                        a[:cnt] = blk.weight[p, st:st + cnt]
                        w["weight_blk"] = a
                arena.append((p, w))
            self._tier_arena[use_rev] = arena
        if self.backend != HYBRID:
            # Cold edges have no resident dict; edges_for raises the fix.
            # (The hybrid backend keeps its binding — its eligible programs
            # tier at ELL-row granularity, and ineligible ones stream the
            # reference-flavor arenas built above.)
            self._fwd = self._rev = None

    # ---------------------- hybrid backend plumbing ------------------------

    def _plan_hybrid(self, k_dense: Optional[int], block_e: int) -> dict:
        """Pick |H| from the perf model (paper Eq. 4 role), or honour an
        explicit ``hybrid_k_dense``; candidates come from the block-span
        histograms' degree-skew signal."""
        from repro.core import perf_model
        from repro.core.hybrid import edge_max_ranks

        g = self.pg.source
        blk = self._fwd_blk or build_block_metadata(self.pg.fwd,
                                                    block_e=block_e)
        skew = blk.degree_skew()
        candidates = perf_model.k_dense_candidates(g.num_vertices,
                                                   skewed=skew > 0.0)
        ranks = edge_max_ranks(g)
        if k_dense is None:
            k_dense, table = perf_model.choose_k_dense(ranks, g.num_edges,
                                                       candidates)
        else:
            table = perf_model.rank_k_dense(
                ranks, g.num_edges, sorted(set(candidates) | {k_dense}))
        chosen = next(r for r in table if r["k_dense"] == k_dense)
        return dict(k_dense=k_dense, candidates=list(candidates), skew=skew,
                    mode=perf_model.split_mode(k_dense, g.num_vertices,
                                               chosen["e_sparse"]),
                    table=table)

    def hybrid_plan(self) -> Optional[dict]:
        """The perf-model split decision (k_dense, mode, ranked table), or
        None when the engine is not the hybrid backend."""
        return self._hybrid_plan

    def _hybrid_semiring(self, program: VertexProgram) -> Optional[str]:
        """Semiring the hybrid backend would run ``program`` under, or None
        when the program is ineligible (no EdgeMessage, or the weight enters
        the message non-separably)."""
        spec = program.edge_msg
        if spec is None:
            return None
        if spec.use_weight:
            if program.combine == MIN and spec.weight_op == "add":
                return "min_plus"
            if program.combine == SUM and spec.weight_op == "mul":
                return "plus_times"
            return None
        return "plus_times" if program.combine == SUM else "min"

    def _uses_hybrid(self, program: VertexProgram) -> bool:
        return (self.backend == HYBRID
                and self._hybrid_semiring(program) is not None)

    def provides_reverse(self, program: VertexProgram) -> bool:
        """True when the engine serves a ``use_reverse`` program without
        ``pg.rev`` (the single-device hybrid degree-splits its own reverse
        graph; the distributed hybrid cannot — boundary edges route through
        the reverse outbox maps, which only ``include_reverse=True``
        partitioning builds)."""
        return self._uses_hybrid(program)

    def _hybrid_key(self, program: VertexProgram):
        # use_weight in the key: a weighted and a weightless program can map
        # to the same semiring (plus_times) but need different ⊗ values
        # (edge weights vs multiplicity counts).  frontier_uniform too: it
        # is baked into the static cfg (bottom-up early-exit licence), and
        # programs sharing a semiring can disagree on it (BFS vs CC).
        return (self._hybrid_semiring(program), program.use_reverse,
                program.edge_msg.use_weight,
                program.edge_msg.frontier_uniform)

    @obs.span(obs.HYBRID_SPLIT)
    def _build_hybrid(self, program: VertexProgram, g, with_push: bool,
                      sliced: bool = False) -> Tuple[_HybridCfg, dict, Any]:
        """One direction's degree split of ``g``: (static cfg, numpy array
        dict, the HybridGraph) — shared by the static cache and the dynamic
        rebuild path.  ``sliced`` packs the ELL as ``HybridGraph.sliced``
        (``ell_width`` holds the chunk widths) and counts its slots under
        ``obs.ELL_PACK``; else the ELL stays rectangular."""
        from repro.core.graph import CSRGraph
        from repro.core.hybrid import degree_split

        semiring = self._hybrid_semiring(program)
        if program.use_reverse:
            g = g.reverse()
        if not program.edge_msg.use_weight and g.weights is not None:
            # The program ignores weights; strip them so the semiring packs
            # multiplicity counts / zero-cost hops instead.
            g = CSRGraph(g.row_ptr, g.col, None)
        hg = degree_split(g, self._hybrid_plan["k_dense"], semiring=semiring)

        asg = self.pg.assignment
        n = g.num_vertices
        slot = (asg.part_of[hg.perm].astype(np.int64) * self.pg.v_max
                + asg.local_id[hg.perm]).astype(np.int32)
        hid = np.full((self.pg.num_parts, self.pg.v_max), n, dtype=np.int32)
        for p, l2g in enumerate(asg.l2g):
            hid[p, : len(l2g)] = hg.inv_perm[l2g]

        arrs = dict(dense=hg.dense_block, ell_col=hg.ell_col,
                    ell_val=hg.ell_val, slot=slot, hid=hid)
        if sliced:
            col, val, widths = hg.sliced()
            arrs.update(ell_col=col, ell_val=val, ell_width=widths)
            obs.count(obs.ELL_PACK, slots=col.size,
                      nonzeros=hg.sparse_edges)
        if with_push and program.combine == MIN and self._direction_switch:
            arrs["push_src"] = hg.inv_perm[g.edge_sources()].astype(np.int32)
            arrs["push_dst"] = hg.inv_perm[g.col].astype(np.int32)
            if semiring == "min_plus" and g.weights is not None:
                arrs["push_w"] = g.weights.astype(np.float32)
            # real (non-sentinel) in-neighbour slots per ELL row — the
            # bottom-up scan kernel's per-row work bound
            arrs["ell_kreal"] = (hg.ell_col != n).sum(axis=1).astype(
                np.int32)

        thr = self._pull_threshold_req
        if thr is None:
            from repro.core import perf_model
            thr = perf_model.fit_pull_threshold(
                g.num_edges / max(n, 1), hg.ell_col.shape[1],
                backend="hybrid")
        cfg = _HybridCfg(semiring=semiring, k_dense=hg.k_dense,
                         num_vertices=n,
                         pull_threshold=float(thr),
                         interpret=self.interpret,
                         forced=self._dopt_forced,
                         uniform=program.edge_msg.frontier_uniform,
                         e_dense=int(hg.k_dense) ** 2)
        return cfg, arrs, hg

    def _hybrid_for(self, program: VertexProgram) -> Tuple[_HybridCfg, dict]:
        """Build (and cache) one direction's degree-split data.

        The cached arrays stay *numpy*: _superstep_hybrid runs at jit-trace
        time, and device arrays created inside one trace must not leak into
        the next (numpy operands become per-trace constants instead)."""
        key = self._hybrid_key(program)
        if key in self._hybrid_cache:
            return self._hybrid_cache[key]
        cfg, arrs, _ = self._build_hybrid(program, self.pg.source,
                                          with_push=True, sliced=True)
        self._hybrid_cache[key] = (cfg, arrs)
        return cfg, arrs

    # Local exchange: outbox[q, p, r] -> inbox[q, r, p] is a transpose over
    # the partition axes (the query axis rides along).
    @staticmethod
    def _exchange(outbox: Array) -> Array:
        return outbox.transpose(0, 2, 1, 3)

    # Single device: each query's apply vote is already its global vote.
    @staticmethod
    def _all_finished(fin: Array) -> Array:
        return fin

    def edges_for(self, program: VertexProgram) -> dict:
        if self.tier_plan is not None and self.backend != HYBRID:
            raise ValueError(
                "engine is tiered (out-of-core): cold partitions' edges "
                "live in host window arenas, not one resident edges dict; "
                "run through execute()/run_batched (the streaming path) or "
                "rebuild the engine without tiered=")
        if program.use_reverse:
            if self._rev is None:
                raise ValueError("program needs reverse edges; partition with "
                                 "include_reverse=True")
            rev = dict(self._rev)
            # reverse direction may have different e/o_max; dims adjust below
            return rev
        return self._fwd

    def fused_cfg_for(self, program: VertexProgram) -> Optional[FusedConfig]:
        """Static fused-path config, or None → reference compute."""
        if not self.fused or program.edge_msg is None:
            return None
        return self._rev_cfg if program.use_reverse else self._fwd_cfg

    def dims_for(self, edges: dict) -> _Dims:
        return _Dims(self.dims.num_parts, self.dims.v_max,
                     edges["src"].shape[1], edges["inbox_dst"].shape[2])

    # ------------------ direction-optimized traversal ----------------------

    def _dopt_semiring(self, program: VertexProgram) -> Optional[str]:
        """Min semiring the reference/fused direction machinery would run
        ``program`` under, or None when ineligible."""
        spec = program.edge_msg
        if spec is None or program.combine != MIN:
            return None
        if spec.use_weight:
            return "min_plus" if spec.weight_op == "add" else None
        return "min"

    def _dopt_cfg_for(self, program: VertexProgram) -> Optional[_DoptCfg]:
        semiring = self._dopt_semiring(program)
        if semiring is None:
            return None
        return _DoptCfg(semiring=semiring,
                        uniform=program.edge_msg.frontier_uniform,
                        forced=self._dopt_forced, interpret=self.interpret)

    def _direction_enabled(self, program: VertexProgram) -> bool:
        """Can ``execute`` thread the direction carry through ``program``?

        Min combines with an EdgeMessage only (direction is a bitwise no-op
        there).  The hybrid backend switches on its push arenas (static and
        dynamic); reference/fused need the transposed layout, which does
        not track mutations — dynamic graphs and tiered engines stay
        push-only, as do ``use_reverse`` programs (their traversal direction
        is already the reverse graph's)."""
        if not self._direction_switch or self.tier_plan is not None:
            return False
        if program.combine != MIN or program.edge_msg is None:
            return False
        if self._uses_hybrid(program):
            return True
        if self.dg is not None or program.use_reverse:
            return False
        if self._dopt_semiring(program) is None:
            return False
        if (self._dopt_semiring(program) == "min_plus"
                and self._pg.fwd.weight is None):
            return False
        return self._fwd is not None

    def _ensure_direction_edges(self) -> None:
        """Lazily grow the forward edges dict with the transposed-ELL
        arrays the pull direction needs (built once per binding; rebinds
        drop them with the dict).  Keys ride the edges dict so they shard
        over the partition axis as ordinary shard_map operands."""
        if self._fwd is None or "t_col" in self._fwd:
            return
        from repro.core import perf_model
        from repro.core.partition import build_transposed_ell

        pg = self._pg
        tell = build_transposed_ell(pg.fwd, pg.v_max)
        vmask = np.asarray(pg.vertex_mask, dtype=bool)
        nreal = np.maximum(vmask.sum(axis=1), 1).astype(np.float64)
        avg = tell.deg_out.sum(axis=1) / nreal
        if self._pull_threshold_req is not None:
            thr = np.full((pg.num_parts, 1), self._pull_threshold_req,
                          np.float32)
        else:
            thr = perf_model.fit_shard_pull_thresholds(
                avg, [tell.kmax] * pg.num_parts,
                backend=self.backend).reshape(-1, 1)
        self._fwd.update(
            t_col=jnp.asarray(tell.col),
            t_kreal=jnp.asarray(tell.kreal),
            t_deg=jnp.asarray(tell.deg_out),
            t_bnd=jnp.asarray(tell.deg_bnd),
            t_vmask=jnp.asarray(vmask),
            t_thr=jnp.asarray(thr.astype(np.float32)))
        if tell.val is not None:
            self._fwd["t_val"] = jnp.asarray(tell.val)

    def _note_path(self, program: VertexProgram) -> None:
        """Count a fused or hybrid engine running ``program`` on the
        reference path (no eligible EdgeMessage) as that backend's "xla"
        path in ``kernels.ops.KERNEL_PATHS``."""
        from repro.kernels.ops import KERNEL_PATHS

        if self.backend != REFERENCE and not (
                self._uses_hybrid(program) or self.fused_cfg_for(program)):
            KERNEL_PATHS[(self.backend, "xla")] += 1

    def _step_fn(self, program: VertexProgram, edges: Optional[dict],
                 exchange: Callable, all_finished: Callable) -> Callable:
        self._note_path(program)
        if self._uses_hybrid(program):
            cfg, arrs = self._hybrid_for(program)
            return functools.partial(_superstep_hybrid, program, cfg, arrs,
                                     all_finished)
        return functools.partial(_superstep, self.dims_for(edges), program,
                                 edges, exchange, all_finished,
                                 self.fused_cfg_for(program),
                                 dopt_cfg=self._dopt_cfg_for(program))

    def _edges_or_none(self, program: VertexProgram) -> Optional[dict]:
        """Edge arrays for the program, or None when the hybrid backend
        serves it (hybrid builds its own reverse direction, so BC runs even
        without ``include_reverse`` partitioning)."""
        return None if self._uses_hybrid(program) else self.edges_for(program)

    @obs.span(obs.EXECUTE)
    def execute(self, program: VertexProgram, state: BatchedState, *,
                num_steps: Optional[int] = None,
                chunk: Optional[int] = None,
                on_chunk: Optional[Callable] = None,
                incremental=None,
                start_step: int = 0, fin=None, steps_q=None,
                max_chunks: Optional[int] = None,
                chaos_ctx: Optional[dict] = None,
                monitor=None):
        """THE engine entry point: one documented facade over every run
        mode.  ``state`` is a batched ``[Q, Pl, v_max]`` pytree
        (:func:`batch_state` lifts a single query).

        Dispatch, by keyword:

        - ``execute(program, state)`` — run-to-convergence: one resident
          ``lax.while_loop``, per-query finished votes, returns
          ``(state, steps_q [Q])``.
        - ``execute(program, state, num_steps=n)`` — fixed-iteration
          programs (PageRank): returns the final ``state``.
        - ``execute(program, state, chunk=k)`` — checkpointable /
          continuous mode: bounded ``k``-superstep windows whose
          boundaries surface the carry to ``on_chunk`` (snapshotting,
          quarantine kills, slot refills — see
          :meth:`run_batched_chunked` for the hook protocol and the
          ``start_step``/``fin``/``steps_q``/``max_chunks`` resume
          operands).  Returns ``(state, steps_q, info)``.
        - ``execute(program, prev_state, incremental=dirty)`` — warm
          start from a previous fixpoint over a ``[Pl, v_max]`` dirty
          mask; returns ``(state, steps_q)`` or ``None`` when the
          program has no :class:`IncrementalForm`.

        Eligible min-combine programs additionally run **direction
        optimized** (docs/traversal.md): execute() threads three [Q, P]
        int32 leaves through the carry (per-shard direction, deterministic
        edges-examined counter, switch counter), strips them from the
        returned state, and records per-query aggregates in
        ``engine.last_direction_stats``.  Chunked/continuous mode stays
        push-only — the slot-refill protocol swaps user state rows and
        must not see engine-internal leaves.

        This is the ONLY public run entry point (the historical
        ``run``/``run_batched``/``run_fixed*``/``run_incremental``/
        ``run_batched_chunked`` aliases are gone — see docs/serving.md for
        the migration table).  The jitted private methods behind each mode
        (``_run_batched``, ``_run_fixed_batched``) remain class attributes
        because their compile cache is the zero-retrace serving contract's
        retrace gate.  Incompatible keyword combinations raise with the
        fix spelled out.
        """
        modes = {"num_steps": num_steps is not None,
                 "chunk": chunk is not None,
                 "incremental": incremental is not None}
        picked = [k for k, v in modes.items() if v]
        if len(picked) > 1:
            raise ValueError(
                f"execute() got {' + '.join(picked)} — these select "
                f"mutually exclusive run modes; pass exactly one (or none "
                f"for run-to-convergence).  Fixed-step chunking is not a "
                f"mode: restate the program with a never-voting apply "
                f"(see _fixed_step_program) and pass chunk= alone.")
        if modes["chunk"] and self.tier_plan is not None:
            raise ValueError(
                "chunked/continuous mode is not supported on a tiered "
                "engine: chunk windows assume resident edge dicts; run "
                "tiered convergence (drop chunk=) or build the engine "
                "without tiered=")
        if not modes["chunk"]:
            chunked_only = [
                name for name, val in (("on_chunk", on_chunk),
                                       ("fin", fin), ("steps_q", steps_q),
                                       ("max_chunks", max_chunks),
                                       ("chaos_ctx", chaos_ctx),
                                       ("monitor", monitor))
                if val is not None] + (
                    ["start_step"] if start_step != 0 else [])
            if chunked_only:
                raise ValueError(
                    f"execute() got {', '.join(chunked_only)} without "
                    f"chunk= — boundary hooks and resume carries only "
                    f"exist in chunked mode; pass chunk=<supersteps per "
                    f"window> (e.g. chunk=2).")
        if modes["chunk"]:
            return self._run_batched_chunked(
                program, state, checkpoint_every=chunk,
                on_chunk=on_chunk, start_step=start_step, fin=fin,
                steps_q=steps_q, max_chunks=max_chunks,
                chaos_ctx=chaos_ctx, monitor=monitor)
        self.last_direction_stats = None
        if modes["incremental"]:
            inc = program.incremental
            if inc is None:
                return None
            # Seed here, then fall through to convergence dispatch so the
            # relaxation program runs direction-optimized too.
            state = inc.seed(state, jnp.asarray(incremental))
            program = inc.program
        use_dopt = isinstance(state, dict) and self._direction_enabled(
            program)
        if use_dopt:
            if not self._uses_hybrid(program):
                self._ensure_direction_edges()
            q = num_queries(state)
            parts = self._pg.num_parts
            state = dict(
                state,
                _dopt_dir=jnp.full((q, parts), -1, jnp.int32),
                _dopt_edges=jnp.zeros((q, parts), jnp.int32),
                _dopt_switch=jnp.zeros((q, parts), jnp.int32))
        if modes["num_steps"]:
            out = self._fixed(program, num_steps, state)
            return self._dopt_finish(out) if use_dopt else out
        out_state, steps_run = self._converge(program, state)
        if use_dopt:
            out_state = self._dopt_finish(out_state)
        return out_state, steps_run

    def _dopt_finish(self, state: BatchedState) -> BatchedState:
        """Strip the direction carry and record per-query aggregates."""
        state = dict(state)
        with obs.span(obs.WAIT):
            d = np.asarray(state.pop(_DOPT_KEYS[0]))
            e = np.asarray(state.pop(_DOPT_KEYS[1]))
            s = np.asarray(state.pop(_DOPT_KEYS[2]))
        self.last_direction_stats = dict(
            direction=d,
            edges_examined=e.sum(axis=1).astype(np.int64),
            switches=s.sum(axis=1).astype(np.int64))
        return state

    def _converge(self, program: VertexProgram,
                  state: BatchedState) -> Tuple[BatchedState, Array]:
        """Run-to-convergence dispatch (dynamic, tiered and distributed
        engines shadow or override it).  The resident edge arrays enter the
        jitted loop as operands: closed over, they would be embedded in the
        program as constants, which at real graph sizes is gigabytes of
        HLO and a second copy in device memory."""
        return self._run_batched(program, self._edges_or_none(program),
                                 state)

    def _fixed(self, program: VertexProgram, num_steps: int,
               state: BatchedState) -> BatchedState:
        """Fixed-iteration dispatch; see :meth:`_converge`."""
        return self._run_fixed_batched(program, num_steps,
                                       self._edges_or_none(program), state)

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def _run_batched(self, program: VertexProgram, edges: Optional[dict],
                     state: BatchedState) -> Tuple[BatchedState, Array]:
        """Advance a [Q, Pl, ...] batch of queries through **one** compiled
        ``lax.while_loop`` until every query votes finish; returns the final
        batched state and per-query superstep counts [Q].  The compiled
        computation is cached on (program, state shape): batches of the same
        Q never retrace, whatever their sources.  Private: dispatch through
        ``execute(program, state)`` — this stays a jitted class attribute
        because its compile cache is the serving contract's retrace gate."""
        step_fn = self._step_fn(program, edges, self._exchange,
                                self._all_finished)
        return _run_batched_loop(step_fn, program.max_steps, state,
                                 num_queries(state))

    @functools.partial(jax.jit, static_argnums=(0, 1, 2))
    def _run_fixed_batched(self, program: VertexProgram, num_steps: int,
                           edges: Optional[dict],
                           state: BatchedState) -> BatchedState:
        """Fixed-iteration algorithms (PageRank), batched over queries.
        Private: dispatch through ``execute(program, state,
        num_steps=n)``."""
        step_fn = self._step_fn(program, edges, self._exchange,
                                self._all_finished)

        def body(i, state):
            state, _ = step_fn(state, i)
            return state

        return jax.lax.fori_loop(0, num_steps, body, state)

    # ---------------------- checkpointable run mode ------------------------

    @functools.partial(jax.jit, static_argnums=(0, 1, 2))
    def _run_chunk(self, program: VertexProgram, chunk: int,
                   edges: Optional[dict], state: BatchedState, step: Array,
                   fin: Array, steps_q: Array, poison: Array):
        self._guard.arm(poison)
        # The checked exchange tags every (partition, peer) slot block; the
        # hybrid step ignores the exchange callable (no outbox on a single
        # device), so its windows report bad == 0 by construction.
        step_fn = self._step_fn(program, edges,
                                _checked_exchange(self._guard),
                                self._all_finished)
        return _run_chunked_loop_guarded(step_fn, self._guard, chunk,
                                         program.max_steps, state, step,
                                         fin, steps_q)

    def _chunk_call(self, program: VertexProgram, chunk: int,
                    state: BatchedState, step: Array, fin: Array,
                    steps_q: Array, poison=None):
        """Dispatch one chunk window; overridden by the distributed engine.
        Returns ``(state, step, fin, steps_q, bad)`` — ``bad`` counts
        exchange-checksum mismatches inside the window (0 on the unguarded
        dynamic paths, whose integrity net is the tombstone/certifier
        layer)."""
        if poison is None:
            poison = jnp.float32(0.0)
        if self.dg is not None:
            self._sync_dynamic()
            if self._uses_hybrid(program):
                cfg, arrs = self._hybrid_dyn_for(program)
                out = _run_dyn_hybrid_chunk_jit(
                    program, cfg, program.max_steps, chunk, arrs, state,
                    step, fin, steps_q)
                return out + (jnp.int32(0),)
            edges = self.edges_for(program)
            dyn = self.dg.payload(program.use_reverse)
            if chaos.visit("tombstone.flip", step=int(step)):
                # Value-level mask flip (a deleted edge resurrects): rides
                # the traced dyn operand, so the window never retraces.
                # Prefer a tombstoned non-self-loop slot — resurrecting a
                # self-loop is inert under every vertex program and would
                # make the corruption drill vacuous.
                tomb_h = np.asarray(dyn["tomb"])
                src_h = np.asarray(edges["src"])
                dst_h = np.asarray(edges["dst_ext"])
                cand = np.flatnonzero(tomb_h[0] & (src_h[0] != dst_h[0]))
                j = int(cand[0]) if cand.size else 0
                dyn = dict(dyn)
                dyn["tomb"] = dyn["tomb"].at[0, j].set(
                    jnp.logical_not(dyn["tomb"][0, j]))
            out = _run_dyn_chunk_jit(
                self.dims_for(edges), program, self.fused_cfg_for(program),
                program.max_steps, chunk, edges, dyn, state, step, fin,
                steps_q)
            return out + (jnp.int32(0),)
        return self._run_chunk(program, chunk, self._edges_or_none(program),
                               state, step, fin, steps_q, poison)

    def _run_batched_chunked(self, program: VertexProgram,
                             state: BatchedState, *, checkpoint_every: int,
                             on_chunk: Optional[Callable] = None,
                             start_step: int = 0, fin=None, steps_q=None,
                             max_chunks: Optional[int] = None,
                             chaos_ctx: Optional[dict] = None,
                             monitor=None):
        """``_run_batched`` in bounded ``checkpoint_every``-superstep chunks.

        Chains :func:`_run_chunked_loop` windows, so the full superstep
        sequence — and every query's result and step count — is **bitwise
        identical** to the single resident while_loop; between windows the
        carry escapes to host.  ``on_chunk(snap)`` receives ``{"state",
        "step", "fin", "steps_q"}`` per chunk and may snapshot it
        (``CheckpointManager.save_tree``) and/or steer the carry:

        - return a ``[Q]`` bool mask → force-finish those queries
          (quarantine: masked queries freeze bitwise exactly like
          converged ones);
        - return a dict → the continuous-batching boundary protocol:
          ``{"kill": mask}`` as above, ``{"refill": (new_rows, admit)}``
          swaps admitted slots' state in via :func:`_slot_swap` (clearing
          their votes and zeroing their step counters — a refilled slot
          joins the resident loop as a fresh query), ``{"stop": True}``
          ends the run at this boundary.  Kills apply before refills, so a
          hook may quarantine a slot and hand it to a new tenant at the
          same boundary.

        The all-finished exit re-checks *after* the hook: a refill that
        clears votes keeps the loop resident, so one ``run_batched_chunked``
        call (and one compiled chunk trace) serves an unbounded query
        stream.  Resume a snapshot by passing its
        ``start_step``/``fin``/``steps_q``.  Returns ``(state, steps_q,
        info)`` with ``info = {"chunks", "final_step", "finished",
        "refilled", "monitors_fired"}``.

        Integrity (docs/robustness.md "Silent faults"): every static-path
        window runs the checksummed exchange — a tag mismatch raises
        :class:`repro.runtime.failures.ExchangeCorruption` *before* the
        corrupted carry replaces the live one, so the caller replays the
        window from its last checkpoint.  ``monitor`` (an object exposing
        ``observe(snap)`` / ``rebase(admit)``, e.g.
        :class:`repro.runtime.verify.InvariantMonitor`) is called once per
        window with the boundary snapshot; its record rides to ``on_chunk``
        under ``snap["monitor"]`` and fired windows are counted in
        ``info["monitors_fired"]``.  The ``state.corrupt`` /
        ``exchange.payload`` chaos sites inject here (host seam / traced
        poison operand — neither perturbs the jit cache).

        Private: dispatch through ``execute(program, state, chunk=k, ...)``.
        """
        if self.tier_plan is not None:
            raise ValueError(
                "chunked/continuous mode is not supported on a tiered "
                "engine: chunk windows assume resident edge dicts; run "
                "tiered convergence instead or build without tiered=")
        if checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {checkpoint_every}")
        q = num_queries(state)
        # restored snapshots arrive as numpy leaves; canonicalize so the
        # resume hits the same jit cache entry as the original run
        state = jax.tree.map(jnp.asarray, state)
        fin = (jnp.zeros((q,), jnp.bool_) if fin is None
               else jnp.asarray(fin, jnp.bool_).reshape(q))
        steps_q = (jnp.zeros((q,), jnp.int32) if steps_q is None
                   else jnp.asarray(steps_q, jnp.int32).reshape(q))
        step = jnp.int32(start_step)
        chunks = 0
        refilled = 0
        monitors_fired = 0
        stop = False
        while True:
            chaos.visit("superstep.chunk", step=int(step), chunk=chunks,
                        **(chaos_ctx or {}))
            if chaos.visit("state.corrupt", step=int(step),
                           **(chaos_ctx or {})):
                state = _flip_state_bit(state)
            poison = jnp.float32(
                1.0 if chaos.visit("exchange.payload", step=int(step),
                                   **(chaos_ctx or {})) else 0.0)
            new_state, new_step, new_fin, new_steps_q, bad = self._chunk_call(
                program, int(checkpoint_every), state, step, fin, steps_q,
                poison)
            n_bad = int(bad)
            if n_bad:
                # The corrupted window never replaces the live carry; the
                # caller's RestartPolicy replays it from the last checkpoint
                # (ExchangeCorruption subclasses WorkerFailure → retryable).
                from repro.runtime.failures import ExchangeCorruption
                raise ExchangeCorruption(
                    f"exchange checksum mismatch in window at superstep "
                    f"{int(step)} ({n_bad} tag(s)): a payload block was "
                    f"corrupted in flight; replay the window from the last "
                    f"checkpoint")
            state, step, fin, steps_q = (new_state, new_step, new_fin,
                                         new_steps_q)
            chunks += 1
            snap = dict(state=state, step=int(step), fin=np.asarray(fin),
                        steps_q=np.asarray(steps_q))
            if monitor is not None:
                rec = monitor.observe(dict(state=state, step=snap["step"],
                                           finished=snap["fin"],
                                           steps_q=snap["steps_q"]))
                monitors_fired += int(rec["violations"] > 0)
                snap["monitor"] = rec
            if on_chunk is not None:
                out = on_chunk(snap)
                if isinstance(out, dict):
                    kill = out.get("kill")
                    if kill is not None:
                        fin = jnp.logical_or(
                            fin, jnp.asarray(kill, jnp.bool_).reshape(q))
                    refill = out.get("refill")
                    if refill is not None:
                        new_rows, admit = refill
                        new_rows = jax.tree.map(jnp.asarray, new_rows)
                        admit = jnp.asarray(admit, jnp.bool_).reshape(q)
                        state, fin, steps_q = _slot_swap(
                            state, new_rows, admit, fin, steps_q)
                        refilled += int(np.asarray(admit).sum())
                        if monitor is not None:
                            monitor.rebase(np.asarray(admit))
                    stop = bool(out.get("stop"))
                elif out is not None:        # legacy bare kill mask
                    fin = jnp.logical_or(
                        fin, jnp.asarray(out, jnp.bool_).reshape(q))
            if stop or bool(jnp.all(fin)) or int(step) >= program.max_steps:
                break
            if max_chunks is not None and chunks >= max_chunks:
                break
        info = dict(chunks=chunks, final_step=int(step),
                    finished=np.asarray(fin), refilled=refilled,
                    monitors_fired=monitors_fired)
        return state, steps_q, info

    # ---------------------- dynamic-graph plumbing -------------------------

    def _sync_dynamic(self) -> None:
        """Rebind after a compaction (the one retrace-paying event); called
        on entry to every dynamic run and by the ``pg`` property."""
        if self.dg.version != self._dyn_version:
            # version first: _bind reads self.pg, whose property getter
            # re-enters this sync — the updated version makes it a no-op.
            self._dyn_version = self.dg.version
            self._bind(self.dg.pg)
            self.dynamic_rebinds += 1

    def _run_batched_dyn(self, program: VertexProgram,
                         state: BatchedState) -> Tuple[BatchedState, Array]:
        """Dynamic-graph ``_run_batched``: same contract, but every graph
        array rides as a traced argument so mutation batches never retrace
        (see ``_run_dyn_jit``)."""
        return self._dispatch_dyn(program, state, fixed_steps=None)

    def _run_fixed_batched_dyn(self, program: VertexProgram, num_steps: int,
                               state: BatchedState) -> BatchedState:
        return self._dispatch_dyn(program, state, fixed_steps=num_steps)

    def _dispatch_dyn(self, program: VertexProgram, state: BatchedState,
                      fixed_steps: Optional[int]):
        self._sync_dynamic()
        if self._uses_hybrid(program):
            cfg, arrs = self._hybrid_dyn_for(program)
            return _run_dyn_hybrid_jit(program, cfg, program.max_steps,
                                       fixed_steps, arrs, state)
        edges = self.edges_for(program)
        dyn = self.dg.payload(program.use_reverse)
        return _run_dyn_jit(self.dims_for(edges), program,
                            self.fused_cfg_for(program), program.max_steps,
                            fixed_steps, edges, dyn, state)

    # ---------------------- tiered (out-of-core) run path ------------------

    def _run_batched_tiered(self, program: VertexProgram,
                            state: BatchedState
                            ) -> Tuple[BatchedState, Array]:
        return self._tiered_run(program, state)

    def _run_fixed_batched_tiered(self, program: VertexProgram,
                                  num_steps: int,
                                  state: BatchedState) -> BatchedState:
        state, _ = self._tiered_run(_fixed_step_program(program, num_steps),
                                    state)
        return state

    def _tiered_run(self, program: VertexProgram, state: BatchedState
                    ) -> Tuple[BatchedState, Array]:
        """Host-driven tiered superstep loop (replaces the resident
        ``lax.while_loop``): hot compute → double-buffered window stream →
        exchange/scatter/apply, per superstep, until every query votes.

        The three jits restate exactly one resident superstep plus the
        batched loop's freeze/vote body, so the fixpoint is **bitwise**
        the resident one.  All shapes (window length, block count, delta
        tail) are static and the streamed partition id is traced — the
        steady state never retraces (``tiered_cache_entries`` is flat
        after the first superstep)."""
        if self.dg is not None:
            self._sync_dynamic()
        if self._uses_hybrid(program):
            return self._tiered_run_hybrid(program, state)
        use_rev = bool(program.use_reverse)
        if use_rev not in self._tier_dims:
            raise ValueError("program needs reverse edges; partition with "
                             "include_reverse=True")
        dims = self._tier_dims[use_rev]
        cfg = None
        if self.fused and program.edge_msg is not None:
            cfg = self._rev_cfg if use_rev else self._fwd_cfg
        hot_idx = self._tier_hot_idx
        edges_hot = self._tier_dev[use_rev]
        arena = self._tier_arena[use_rev]
        inbox_dst = self._tier_inbox[use_rev]

        dyn_hot = None
        stream = [(p, w, cfg) for p, w in arena]
        if self.dg is not None:
            dyn = self.dg.payload(use_rev)
            inbox_dst = dyn["inbox_dst"]
            hot_np = np.asarray(self.tier_plan.hot, np.int64)
            if edges_hot is not None:
                dyn_hot = dict(tomb=dyn["tomb"][hot_np],
                               d_src=dyn["d_src"][hot_np],
                               d_dst_ext=dyn["d_dst_ext"][hot_np])
                if "d_weight" in dyn:
                    dyn_hot["d_weight"] = dyn["d_weight"][hot_np]
            # Cold mutations stream with their partitions: tombstone slices
            # ride inside each base window; the inserted-edge delta slots
            # become one reference-flavor window per cold partition at the
            # end of the stream (per-segment order is base ⊕ delta — the
            # same order the resident dynamic superstep combines in).
            tomb_h = np.asarray(dyn["tomb"])
            sched = self.tier_plan.rev if use_rev else self.tier_plan.fwd
            stream = []
            for (p, w), st, cnt in zip(arena, sched.start, sched.count):
                st, cnt = int(st), int(cnt)
                t = np.zeros(w["src"].shape[0], bool)
                t[:cnt] = tomb_h[p, st:st + cnt]
                stream.append((p, dict(w, tomb=t), cfg))
            d_src = np.asarray(dyn["d_src"])
            d_dst = np.asarray(dyn["d_dst_ext"])
            d_w = np.asarray(dyn["d_weight"]) if "d_weight" in dyn else None
            for p in np.asarray(self.tier_plan.cold, np.int64):
                p = int(p)
                dwin = dict(src=d_src[p], dst_ext=d_dst[p])
                if d_w is not None:
                    dwin["weight"] = d_w[p]
                stream.append((p, dwin, None))

        q = num_queries(state)
        fin = jnp.zeros((q,), jnp.bool_)
        steps_q = jnp.zeros((q,), jnp.int32)
        step = 0
        while True:
            acc = _tiered_hot_jit(dims, program, cfg, hot_idx, edges_hot,
                                  dyn_hot, state, jnp.int32(step))
            # double buffer: block w+1's host→device put is in flight while
            # the compute consumes block w
            nxt = jax.device_put(stream[0][1]) if stream else None
            for i, (p, _, wcfg) in enumerate(stream):
                cur = nxt
                nxt = (jax.device_put(stream[i + 1][1])
                       if i + 1 < len(stream) else None)
                acc = _tiered_window_jit(dims, program, wcfg, p, acc, cur,
                                         state, jnp.int32(step))
            state, fin, steps_q = _tiered_apply_jit(
                dims, program, acc, inbox_dst, state, jnp.int32(step), fin,
                steps_q)
            step += 1
            if step >= program.max_steps or bool(jnp.all(fin)):
                break
        return state, steps_q

    def _hybrid_tiered_for(self, program: VertexProgram):
        """Row-tiered ELL split for one program: hot-partition rows stay a
        resident compacted ELL; cold rows are chunked into fixed-shape host
        windows (sentinel-padded).  Pull-only — min over the same value
        multiset is exact in either direction, so parity with the resident
        (possibly push-switching) hybrid holds bitwise."""
        from repro.kernels.ell_spmv import SEMIRINGS

        key = self._hybrid_key(program)
        if key in self._hyb_tier_cache:
            return self._hyb_tier_cache[key]
        cfg, arrs, _ = self._build_hybrid(program, self.pg.source,
                                          with_push=False)
        n = cfg.num_vertices
        mul_ident = SEMIRINGS[cfg.semiring][2]
        ell_col = np.asarray(arrs["ell_col"])
        ell_val = np.asarray(arrs["ell_val"])
        kmax = ell_col.shape[1]
        part_of_row = np.asarray(arrs["slot"]).astype(np.int64) \
            // self.pg.v_max
        cold = np.asarray(self.tier_plan.cold, np.int64)
        is_cold = np.isin(part_of_row, cold)
        rows = np.arange(n, dtype=np.int64)
        hot_rows, cold_rows = rows[~is_cold], rows[is_cold]
        if len(hot_rows):
            hot_dev = (jnp.asarray(ell_col[hot_rows]),
                       jnp.asarray(ell_val[hot_rows]),
                       jnp.asarray(hot_rows.astype(np.int32)))
        else:
            hot_dev = (None, None, jnp.zeros((0,), jnp.int32))
        wins = []
        if len(cold_rows):
            win_rows = max(8, min(len(cold_rows), self._block_e))
            for s in range(0, len(cold_rows), win_rows):
                sel = cold_rows[s:s + win_rows]
                m = len(sel)
                col = np.full((win_rows, kmax), n, ell_col.dtype)
                val = np.full((win_rows, kmax), mul_ident, ell_val.dtype)
                r = np.full((win_rows,), n, np.int32)  # pad rows → sink
                col[:m], val[:m], r[:m] = ell_col[sel], ell_val[sel], sel
                wins.append(dict(col=col, val=val, rows=r))
        acc_fn = _make_tiered_hyb_acc(cfg, np.asarray(arrs["dense"]),
                                      np.asarray(arrs["hid"]))
        ent = (cfg, jnp.asarray(arrs["slot"]), hot_dev, acc_fn, wins)
        self._hyb_tier_cache[key] = ent
        return ent

    def _tiered_run_hybrid(self, program: VertexProgram, state: BatchedState
                           ) -> Tuple[BatchedState, Array]:
        cfg, slot, hot_dev, acc_fn, wins = self._hybrid_tiered_for(
            program)
        col_hot, val_hot, rows_hot = hot_dev
        q = num_queries(state)
        fin = jnp.zeros((q,), jnp.bool_)
        steps_q = jnp.zeros((q,), jnp.int32)
        step = 0
        while True:
            xs, y = _tiered_hyb_hot_jit(program, cfg, slot, col_hot,
                                        val_hot, rows_hot, state,
                                        jnp.int32(step))
            nxt = jax.device_put(wins[0]) if wins else None
            for i in range(len(wins)):
                cur = nxt
                nxt = (jax.device_put(wins[i + 1])
                       if i + 1 < len(wins) else None)
                y = _tiered_hyb_win_jit(cfg, y, cur["col"], cur["val"],
                                        cur["rows"], xs)
            acc = acc_fn(y, xs)
            state, fin, steps_q = _tiered_hyb_apply_jit(
                program, acc, state, jnp.int32(step), fin, steps_q)
            step += 1
            if step >= program.max_steps or bool(jnp.all(fin)):
                break
        return state, steps_q

    def tiered_cache_entries(self) -> int:
        """Compile-cache entries across the tiered jits (module-level plus
        this engine's per-binding hybrid acc closures; the zero-retrace
        gates diff this between supersteps/runs)."""
        extra = sum(_cache_entries_of(ent[3])
                    for ent in getattr(self, "_hyb_tier_cache", {}).values())
        return tiered_cache_entries() + extra

    def residency_bytes(self, state_bytes: int = 4) -> dict:
        """``{"hbm_bytes", "host_bytes", "total_bytes"}`` for the bound
        layout under this engine's tier plan (all-resident without one);
        serving admission must charge only ``hbm_bytes`` against device
        capacity."""
        from repro.core.partition import memory_residency_bytes

        return memory_residency_bytes(self._pg, tier_plan=self.tier_plan,
                                      state_bytes=state_bytes,
                                      dynamic=self.dg)

    def tiered_stats(self) -> Optional[dict]:
        """Deterministic out-of-core counters for the bench/report column,
        or None on an all-resident engine."""
        if self.tier_plan is None:
            return None
        plan = self.tier_plan
        return dict(hbm_resident_bytes=int(plan.hbm_bytes),
                    host_bytes=int(plan.host_bytes),
                    streamed_bytes_per_superstep=int(
                        plan.streamed_bytes_per_superstep),
                    window_count=int(plan.window_count),
                    num_hot=len(plan.hot), num_cold=len(plan.cold))

    def should_resplit_hybrid(self, threshold: float = 0.10) -> bool:
        """The ``perf_model.should_resplit`` rule, applied to this engine's
        frozen dynamic-hybrid split: re-evaluate the candidate ladder on
        the *mutated* graph's degree ranks and vote to re-rank only when
        the predicted makespan improves by more than ``threshold``.  The
        serving driver calls this per round and consumes a True vote as a
        compaction (rebinding re-runs ``_plan_hybrid`` on the mutated
        graph).  False on non-hybrid/static engines; the distributed
        hybrid re-plans at its forced compactions anyway.
        """
        if self.dg is None or self.backend != HYBRID:
            return False
        from repro.core import perf_model
        from repro.core.hybrid import edge_max_ranks

        g = self.dg.mutated_csr()
        resplit, info = perf_model.should_resplit(
            edge_max_ranks(g), g.num_edges, self._hybrid_plan["candidates"],
            current_k=self._hybrid_plan["k_dense"], threshold=threshold)
        self.last_resplit_info = info
        return resplit

    def _hybrid_dyn_for(self, program: VertexProgram
                        ) -> Tuple[_HybridCfg, dict]:
        """The dynamic hybrid split: device arrays kept in sync with the
        mutation log.

        Deletions write ⊕-identity (or the post-delete combine of surviving
        parallel edges) into the dense block / clear ELL entries; insertions
        land in the dense block or in the **spare ELL columns** reserved at
        build time.  The degree *ranking* stays frozen between compactions
        (a stale split is a performance choice, never a correctness one —
        ``perf_model.should_resplit`` decides when re-ranking pays).  A row
        running out of spare columns triggers a full rebuild of this
        split from the mutated CSR.
        """
        key = self._hybrid_key(program)
        ent = self._hybrid_dyn_cache.get(key)
        if ent is not None and ent["cursor"] < self.dg.log_floor:
            # the bounded batch log no longer reaches back to this entry's
            # cursor: rebuild from the mutated CSR
            ent = None
            self.hybrid_dyn_rebuilds += 1
        if ent is None:
            ent = self._build_hybrid_dyn(program)
            self._hybrid_dyn_cache[key] = ent
        pending = [rec for rec in self.dg._batch_log
                   if rec["index"] > ent["cursor"]]
        if pending:
            pairs = set()
            for rec in pending:
                b = rec["batch"]
                pairs.update(zip(b.src.tolist(), b.dst.tolist()))
            try:
                self._reconcile_hybrid(ent, key, pairs)
            except _EllOverflow:
                ent = self._build_hybrid_dyn(program)
                self._hybrid_dyn_cache[key] = ent
                self.hybrid_dyn_rebuilds += 1
            ent["cursor"] = self.dg.num_batches
        return ent["cfg"], ent["arrs"]

    def _build_hybrid_dyn(self, program: VertexProgram) -> dict:
        from repro.kernels.ell_spmv import SEMIRINGS

        cfg, arrs, hg = self._build_hybrid(program, self.dg.mutated_csr(),
                                           with_push=True)
        n = cfg.num_vertices
        mul_ident = SEMIRINGS[cfg.semiring][2]
        spare = self._dyn_ell_spare
        ell_col = np.pad(hg.ell_col, ((0, 0), (0, spare)),
                         constant_values=n)
        ell_val = np.pad(hg.ell_val, ((0, 0), (0, spare)),
                         constant_values=mul_ident)
        arrs = dict(arrs, ell_col=ell_col, ell_val=ell_val)
        push_extra = dict(push_src=None, push_dst=None, push_w=None)
        if "push_src" in arrs:
            # Push arenas ride mutations too: spare sentinel slots
            # (src = dst = n, inert under the extended-segment reduce) take
            # inserts, deletes tombstone slots back to the sentinel, and the
            # capacity is pow2-rounded so a post-growth rebuild usually
            # lands on shapes the jit cache has already seen.
            e = int(arrs["push_src"].shape[0])
            need = e + max(4 * self.dg.mutation_capacity, 64)
            cap = 1 << (need - 1).bit_length()
            push_src = np.pad(arrs["push_src"], (0, cap - e),
                              constant_values=n)
            push_dst = np.pad(arrs["push_dst"], (0, cap - e),
                              constant_values=n)
            arrs = dict(arrs, push_src=push_src, push_dst=push_dst)
            if "push_w" in arrs:
                arrs["push_w"] = np.pad(arrs["push_w"], (0, cap - e),
                                        constant_values=0.0)
            # Reconcile fills spare ELL columns out of slot order, so the
            # bottom-up scan's per-row bound must cover the full (spared)
            # width — early exit still cuts the live-parent common case.
            arrs["ell_kreal"] = np.full(n, ell_col.shape[1], np.int32)
            pair_slots: dict = {}
            for j in range(e):
                pair_slots.setdefault(
                    (int(push_src[j]), int(push_dst[j])), []).append(j)
            push_extra = dict(
                push_src=push_src.copy(), push_dst=push_dst.copy(),
                push_w=(arrs["push_w"].copy() if "push_w" in arrs
                        else None),
                pair_slots=pair_slots, push_free=list(range(e, cap)))
        ent = dict(
            cfg=cfg,
            arrs={k: jnp.asarray(v) for k, v in arrs.items()},
            # host mirrors for entry location + free-slot scans
            dense=np.asarray(arrs["dense"]).copy(),
            ell_col=ell_col.copy(), ell_val=ell_val.copy(),
            inv_perm=hg.inv_perm, mul_ident=float(mul_ident),
            cursor=self.dg.num_batches)
        ent.update(push_extra)
        return ent

    def _reconcile_hybrid(self, ent: dict, key, pairs) -> None:
        """Reconcile the split's ⊗ values for every touched (u, v) pair
        against the ledger's current live multiset, then apply every write
        — dense block, ELL pull layout, *and* the push arenas — through the
        **one** compiled padded scatter the mutation path already uses
        (``dynamic._scatter_payload``): both traversal layouts stay in sync
        out of a single device dispatch, and the compiled superstep only
        ever sees the arrays as operands."""
        from repro.core.dynamic import _scatter_payload
        from repro.core.hybrid import add_identity

        semiring, use_reverse, use_weight = key[:3]
        cfg = ent["cfg"]
        inv, k = ent["inv_perm"], cfg.k_dense
        ident = add_identity(semiring)
        n = cfg.num_vertices
        writes = {m: {} for m in ("dense", "ell_col", "ell_val",
                                  "push_src", "push_dst", "push_w")}
        for (u, v) in pairs:
            a, b = (v, u) if use_reverse else (u, v)
            ha, hb = int(inv[a]), int(inv[b])
            weights = self.dg.ledger.alive_weights(u, v)
            if semiring == "plus_times":
                vals = [float(w) if use_weight else 1.0 for w in weights]
            elif semiring == "min_plus":
                vals = [float(w) if use_weight else 0.0 for w in weights]
            else:
                vals = [0.0] * len(weights)
            if k and ha < k and hb < k:
                if not vals:
                    cell = ident
                elif semiring == "plus_times":
                    acc = np.float32(0.0)   # f32 accumulation, like add.at
                    for x in vals:
                        acc = np.float32(acc + np.float32(x))
                    cell = float(acc)
                else:
                    cell = min(vals)
                writes["dense"][ha * k + hb] = cell
            else:
                self._reconcile_ell_row(ent, hb, ha, vals, n,
                                        writes["ell_col"],
                                        writes["ell_val"])
            if ent.get("push_src") is not None:
                self._reconcile_push(ent, ha, hb, vals, n, writes)
        for flat, val in writes["dense"].items():
            ent["dense"].reshape(-1)[flat] = val
        for mkey in ("ell_col", "ell_val"):
            mirror = ent[mkey].reshape(-1)
            for flat, val in writes[mkey].items():
                mirror[flat] = val
        # One compiled scatter over a fixed key set with pow2-padded write
        # widths: batches of any composition reuse the same trace.
        live = {m: w for m, w in writes.items() if m in ent["arrs"]}
        payload = {m: ent["arrs"][m] for m in live}
        upd = {}
        for m, w in live.items():
            arr = payload[m]
            width = 1 << (max(len(w), 1) - 1).bit_length()
            idx = np.full(width, arr.size, dtype=np.int64)  # drop sentinel
            val = np.zeros(width, dtype=arr.dtype)
            if w:
                idx[:len(w)] = np.fromiter(w.keys(), dtype=np.int64,
                                           count=len(w))
                val[:len(w)] = np.asarray(list(w.values()), dtype=arr.dtype)
            upd[m] = (jnp.asarray(idx), jnp.asarray(val))
        out = _scatter_payload(payload, upd)
        for m in live:
            ent["arrs"][m] = out[m]

    def _reconcile_push(self, ent: dict, ha: int, hb: int, vals,
                        sentinel: int, writes: dict) -> None:
        """Match the push arena's (ha → hb) slots to the live multiset:
        tombstone extras back to the sentinel, claim spare slots for new
        edges.  Weightless arenas match by count; min_plus by ⊗ value.
        Raises :class:`_EllOverflow` when the spare pool runs dry (the
        caller rebuilds from the mutated CSR)."""
        slots = ent["pair_slots"].setdefault((ha, hb), [])
        w = ent["push_w"]
        if w is None:
            keep, extras = slots[:len(vals)], slots[len(vals):]
            remaining = vals[len(slots):]
        else:
            remaining, keep, extras = list(vals), [], []
            for j in slots:
                x = float(w[j])
                if x in remaining:
                    remaining.remove(x)
                    keep.append(j)
                else:
                    extras.append(j)
        for j in extras:
            writes["push_src"][j] = sentinel
            writes["push_dst"][j] = sentinel
            ent["push_src"][j] = sentinel
            ent["push_dst"][j] = sentinel
            if w is not None:
                writes["push_w"][j] = 0.0
                w[j] = 0.0
            ent["push_free"].append(j)
        if remaining:
            free = ent["push_free"]
            if len(free) < len(remaining):
                raise _EllOverflow((ha, hb))
            for x in remaining:
                j = free.pop()
                writes["push_src"][j] = ha
                writes["push_dst"][j] = hb
                ent["push_src"][j] = ha
                ent["push_dst"][j] = hb
                if w is not None:
                    writes["push_w"][j] = float(x)
                    w[j] = float(x)
                keep.append(j)
        ent["pair_slots"][(ha, hb)] = keep

    def _reconcile_ell_row(self, ent: dict, row: int, col: int, want,
                           sentinel: int, col_w: dict, val_w: dict) -> None:
        """Match row ``row``'s entries with column ``col`` to the live
        multiset ``want`` (add into sentinel slots, clear extras)."""
        col_row = ent["ell_col"][row]
        val_row = ent["ell_val"][row]
        kmax = col_row.shape[0]
        have = [int(j) for j in np.flatnonzero(col_row == col)]
        remaining = list(want)
        keep = []
        for j in have:
            v = float(val_row[j])
            if v in remaining:
                remaining.remove(v)
                keep.append(j)
        extras = [j for j in have if j not in keep]
        for j in extras:
            flat = row * kmax + j
            col_w[flat] = sentinel
            val_w[flat] = ent["mul_ident"]
            col_row[j] = sentinel          # keep the free-slot scan honest
            val_row[j] = ent["mul_ident"]
        if remaining:
            free = [int(j) for j in np.flatnonzero(col_row == sentinel)]
            if len(free) < len(remaining):
                raise _EllOverflow(row)
            for j, v in zip(free, remaining):
                flat = row * kmax + j
                col_w[flat] = col
                val_w[flat] = v
                col_row[j] = col
                val_row[j] = v


class _EllOverflow(RuntimeError):
    """A dynamic hybrid ELL row ran out of spare columns (full rebuild)."""


class DistributedBSPEngine(BSPEngine):
    """Partitions sharded over a mesh axis with shard_map.

    One (or more) partition(s) per device; the exchange phase becomes an
    ``all_to_all`` over the mesh axis — the ICI analogue of the paper's PCI-E
    outbox/inbox copy.  The termination vote is a global AND (psum).

    ``backend="hybrid"`` runs the paper's actual headline configuration:
    every shard executes its own degree-split two-engine step over its
    intra-partition edges while boundary messages are aggregated into outbox
    slots at the source and exchanged through a *compact* ``all_to_all``
    that ships only the used (shard, peer) slot blocks.  The per-shard
    split sizes come from the comm-inclusive performance model
    (``perf_model.plan_shards``, Eq. 1–2); ``hybrid_plan()`` reports them.
    Unlike the single-device hybrid, ``use_reverse`` programs (BC) need
    ``include_reverse=True`` partitioning — the reverse boundary edges
    route through the reverse outbox maps.
    """

    def __init__(self, pg, mesh: Mesh, axis: str = "parts", **kwargs):
        from repro.core.dynamic import DynamicGraph

        if kwargs.get("tiered") is not None:
            raise ValueError(
                "tiered= is single-device only: the distributed engine's "
                "shard_map superstep has no host-streaming seam yet; drop "
                "tiered= or use BSPEngine")
        inner = pg.pg if isinstance(pg, DynamicGraph) else pg
        if inner.num_parts % mesh.shape[axis]:
            raise ValueError("num_parts must divide mesh axis size")
        self.mesh = mesh
        self.axis = axis
        super().__init__(pg, **kwargs)

    def _bind(self, pg: PartitionedGraph) -> None:
        self._hybrid_dist_cache: dict = {}
        super()._bind(pg)

    def _sync_dynamic(self) -> None:
        # The distributed hybrid's compact-exchange maps (send_idx/recv_ids)
        # are static used-slot sets: in-place deltas cannot extend them, so
        # pending mutations are consumed through compaction instead (the
        # in-place spare-slot exchange is future work — docs/dynamic.md).
        if self.backend == HYBRID and self.dg.batches_in_version:
            self.dg.compact()
        super()._sync_dynamic()

    def _run_batched_dyn(self, program: VertexProgram,
                         state: BatchedState) -> Tuple[BatchedState, Array]:
        self._sync_dynamic()
        # The sharded path is already stale-constant-safe: edge arrays and
        # the mutation payload travel as shard_map operands rebuilt from the
        # engine's current binding on every call (see _dist_step_parts).
        return DistributedBSPEngine._converge(self, program, state)

    def should_resplit_hybrid(self, threshold: float = 0.10) -> bool:
        # the distributed hybrid consumes mutations via forced compactions,
        # each of which already re-runs plan_shards on the mutated graph
        return False

    def _run_fixed_batched_dyn(self, program: VertexProgram, num_steps: int,
                               state: BatchedState) -> BatchedState:
        # Fixed-step programs must ride the *sharded* path too (the base
        # dynamic runner's local exchange/vote would silently unshard the
        # run): a never-finished program variant turns the distributed
        # while_loop into an exact num_steps round count.
        state, _ = self._run_batched_dyn(
            _fixed_step_program(program, num_steps), state)
        return state

    # ------------------- distributed hybrid plumbing -----------------------

    def provides_reverse(self, program: VertexProgram) -> bool:
        # The distributed hybrid routes reverse boundary edges through the
        # reverse outbox maps, so pg.rev is required even for the hybrid.
        return False

    def _plan_hybrid(self, k_dense: Optional[int], block_e: int) -> dict:
        """Per-shard split decision: each shard's |H| is the argmin of its
        own comm-inclusive predicted makespan (Eq. 1 with the §3.4 reduced
        boundary term); the system prediction is the max over shards
        (Eq. 2)."""
        from repro.core import perf_model
        from repro.core.hybrid import _shard_intra, shard_plan_inputs

        num_shards = self.mesh.shape[self.axis]
        # Forward-direction shard layouts are shared with the split builder
        # (_hybrid_dist_for) — the O(|E| + V log V) ranking runs once.
        self._shard_layouts = _shard_intra(self.pg, num_shards,
                                           self.pg.source)
        ranks, edges, slots, nverts = shard_plan_inputs(
            self.pg, num_shards, layouts=self._shard_layouts)
        blk = self._fwd_blk or build_block_metadata(self.pg.fwd,
                                                    block_e=block_e)
        skew = blk.degree_skew()
        candidates = [perf_model.k_dense_candidates(n, skewed=skew > 0.0)
                      for n in nverts]
        plan = perf_model.plan_shards(ranks, edges, slots, candidates,
                                      k_dense=k_dense)
        for rec, n in zip(plan["per_shard"], nverts):
            rec["mode"] = perf_model.split_mode(rec["k_dense"], n,
                                                rec["e_sparse"])
        plan.update(skew=skew, num_shards=num_shards, candidates=candidates)
        return plan

    def _hybrid_dist_for(self, program: VertexProgram):
        """Build (and cache) one direction's per-shard split: the static
        :class:`hybrid.ShardHybridData` plus its device arrays, sharded over
        the mesh axis."""
        from repro.core.hybrid import shard_degree_split

        semiring = self._hybrid_semiring(program)
        # use_weight in the key for the same reason as _hybrid_for: one
        # semiring can serve weighted and weightless programs, whose splits
        # pack different ⊗ values.
        key = (semiring, program.use_reverse, program.edge_msg.use_weight)
        if key in self._hybrid_dist_cache:
            return self._hybrid_dist_cache[key]

        shd = shard_degree_split(
            self.pg, self.mesh.shape[self.axis], semiring,
            [rec["k_dense"] for rec in self._hybrid_plan["per_shard"]],
            use_reverse=program.use_reverse,
            use_weights=program.edge_msg.use_weight,
            direction_switch=(program.combine == MIN
                              and self._direction_switch),
            layouts=self._shard_layouts)
        arrs = dict(n_vert=shd.n_vert, dense=shd.dense, ell_col=shd.ell_col,
                    ell_val=shd.ell_val, slot=shd.slot, hid=shd.hid,
                    b_src=shd.b_src, b_local=shd.b_local, b_ids=shd.b_ids,
                    b_mask=shd.b_mask, send_idx=shd.send_idx,
                    recv_ids=shd.recv_ids, loc_idx=shd.loc_idx,
                    loc_ids=shd.loc_ids)
        if shd.b_weight is not None:
            arrs["b_weight"] = shd.b_weight
        if shd.push_src is not None:
            arrs["push_src"] = shd.push_src
            arrs["push_dst"] = shd.push_dst
            if shd.push_w is not None:
                arrs["push_w"] = shd.push_w
            # direction-optimization operands, per shard: real ELL slot
            # counts (bottom-up scan bound), the perf-model-fitted
            # push/pull crossover, and the static dense-stage work charge
            arrs["ell_kreal"] = (shd.ell_col
                                 != shd.n_max).sum(axis=2).astype(np.int32)
            ks = [int(rec["k_dense"])
                  for rec in self._hybrid_plan["per_shard"]]
            arrs["e_dense"] = np.asarray(
                [[k * k] for k in ks], dtype=np.int32)
            if self._pull_threshold_req is not None:
                thr = np.full((shd.num_shards, 1, 1),
                              self._pull_threshold_req, np.float32)
            else:
                from repro.core import perf_model
                nv = np.maximum(np.asarray(shd.n_vert,
                                           np.float64).reshape(-1), 1.0)
                intra = (np.asarray(arrs["ell_kreal"], np.int64).sum(axis=1)
                         + np.asarray(ks, np.int64) ** 2)
                thr = perf_model.fit_shard_pull_thresholds(
                    intra / nv, [shd.ell_col.shape[2]] * shd.num_shards,
                    backend="hybrid").reshape(-1, 1, 1)
            arrs["pull_thr"] = thr.astype(np.float32)
        sharding = jax.sharding.NamedSharding(self.mesh, P(self.axis))
        arrs = {k: jax.device_put(jnp.asarray(v), sharding)
                for k, v in arrs.items()}
        self._hybrid_dist_cache[key] = (shd, arrs)
        return shd, arrs

    def _hybrid_step_fn(self, program: VertexProgram, shd, arrs,
                        guard=None) -> Callable:
        return functools.partial(_superstep_hybrid_dist, program, shd, arrs,
                                 self.axis, self.interpret,
                                 self._pull_threshold, self._dist_finished,
                                 guard=guard,
                                 n_shards=self.mesh.shape[self.axis],
                                 forced=self._dopt_forced,
                                 uniform=program.edge_msg.frontier_uniform)

    # ----------------------------- exchange --------------------------------

    def _dist_exchange(self, outbox: Array) -> Array:
        # outbox: [Q, pl, P, o_max] -> split peer axis across devices, concat
        # the received blocks on a device axis, then restore layout (a 3-D
        # input is treated as a single query).
        if outbox.ndim == 3:
            return self._dist_exchange(outbox[None])[0]
        chaos.visit("exchange", axis=self.axis)
        q, pl, peers, o = outbox.shape
        n_dev = self.mesh.shape[self.axis]
        if peers != n_dev * pl:
            raise ValueError(
                f"outbox shape {tuple(outbox.shape)} is inconsistent with "
                f"the mesh: peer axis ({peers}) must equal mesh axis size "
                f"({n_dev}) × local partitions ({pl}).  Every device must "
                f"host the same number of partitions — repartition so "
                f"num_parts == {n_dev} × pl")
        # regroup peer axis as (device, local_partition)
        ob = outbox.reshape(q, pl, n_dev, pl, o)
        recv = jax.lax.all_to_all(ob, self.axis, split_axis=2, concat_axis=0,
                                  tiled=False)
        # recv: [n_dev, Q, pl_src, pl_dst, o] — reorder to
        # inbox[Q, pl_local, P_global, o]
        recv = recv.transpose(1, 3, 0, 2, 4)  # [Q, pl_dst, n_dev, pl_src, o]
        return recv.reshape(q, pl, n_dev * pl, o)

    def _checked_dist_exchange(self, guard) -> Callable[[Array], Array]:
        """:meth:`_dist_exchange` with per-(shard, peer-partition) reduction
        tags: send-side tags ship over their own ``all_to_all`` and the
        inbox side re-derives them — a wire flip lands in the guard and the
        host replays the window (see ``_checked_exchange``)."""
        n_dev = self.mesh.shape[self.axis]
        axis = self.axis

        def exchange(outbox: Array) -> Array:
            if outbox.ndim == 3:
                return exchange(outbox[None])[0]
            chaos.visit("exchange", axis=axis)
            q, pl, peers, o = outbox.shape
            if peers != n_dev * pl:
                raise ValueError(
                    f"outbox shape {tuple(outbox.shape)} is inconsistent "
                    f"with the mesh: peer axis ({peers}) must equal mesh "
                    f"axis size ({n_dev}) × local partitions ({pl})")
            ob = outbox.reshape(q, pl, n_dev, pl, o)
            send_tags = _payload_tag(ob, (0, 4))  # [pl_src, n_dev, pl_dst]
            ob = jnp.where(guard.poison > 0, _flip_wire(ob), ob)
            recv = jax.lax.all_to_all(ob, axis, split_axis=2,
                                      concat_axis=0, tiled=False)
            want = jax.lax.all_to_all(send_tags, axis, split_axis=1,
                                      concat_axis=0, tiled=False)
            got = _payload_tag(recv, (1, 4))   # [n_dev_src, pl_src, pl_dst]
            guard.add(jnp.sum((got != want).astype(jnp.int32)))
            recv = recv.transpose(1, 3, 0, 2, 4)
            return recv.reshape(q, pl, n_dev * pl, o)

        return exchange

    def _dist_finished(self, fin: Array) -> Array:
        # fin: [Q] per-shard votes -> [Q] global AND over the mesh axis.
        not_done = jnp.logical_not(fin).astype(jnp.int32)
        return jax.lax.psum(not_done, self.axis) == 0

    def _validate_state(self, state: BatchedState) -> None:
        """Fail fast on mis-sharded inputs: every [Q, num_parts, ...] leaf
        must split evenly over the mesh axis (the exchange silently
        mis-routes otherwise)."""
        leaves = jax.tree_util.tree_leaves_with_path(state)
        for path, leaf in leaves:
            shape = getattr(leaf, "shape", ())
            if len(shape) >= 2 and shape[1] != self.pg.num_parts:
                raise ValueError(
                    f"state leaf {jax.tree_util.keystr(path)} has partition "
                    f"axis {shape[1]}, expected num_parts="
                    f"{self.pg.num_parts}: every device must host the same "
                    f"number of partitions")

    # ------------------------------- run -----------------------------------

    def _dist_step_parts(self, program: VertexProgram, guard=None):
        """Shared run()/superstep() dispatch: the sharded extra operands
        (hybrid shard arrays — already device_put — or edge arrays, plus the
        dynamic mutation payload when the graph mutates) and a factory
        building the per-shard step function from them.  With ``guard``,
        every exchange runs checksummed (chunked windows pass the engine
        guard; the unguarded ``run``/``superstep`` paths pass None)."""
        self._note_path(program)
        if self._uses_hybrid(program):
            shd, arrs = self._hybrid_dist_for(program)
            return arrs, (lambda extra:
                          self._hybrid_step_fn(program, shd, extra,
                                               guard=guard)), True
        exchange = (self._dist_exchange if guard is None
                    else self._checked_dist_exchange(guard))
        edges = self.edges_for(program)
        dims = self.dims_for(edges)

        if self.dg is not None:
            # tomb/delta/inbox arrays share the edges' partition axis, so
            # they shard under the same spec and slice per device.
            extra = {"edges": edges,
                     "dyn": self.dg.payload(program.use_reverse)}

            def make_dyn(ex):
                return functools.partial(_superstep, dims, program,
                                         ex["edges"], exchange,
                                         self._dist_finished,
                                         self.fused_cfg_for(program),
                                         dyn=ex["dyn"])

            return extra, make_dyn, False

        def make(extra):
            return functools.partial(_superstep, dims, program, extra,
                                     exchange,
                                     self._dist_finished,
                                     self.fused_cfg_for(program),
                                     dopt_cfg=self._dopt_cfg_for(program))

        return edges, make, False

    def _converge(self, program: VertexProgram,
                  state: BatchedState) -> Tuple[BatchedState, Array]:
        """Advance a [Q, P, ...] batch of queries through one sharded
        ``lax.while_loop``; the termination vote is a per-query global AND
        (psum over the mesh axis).  Returns (batched state, steps [Q]).
        Private: dispatch through ``execute(program, state)``."""
        self._validate_state(state)
        q = num_queries(state)
        # State shards on the *partition* axis (axis 1); the query axis is
        # replicated-free: every device holds all Q rows of its partitions.
        spec = P(None, self.axis)
        extra_spec = P(self.axis)
        sharding = jax.sharding.NamedSharding(self.mesh, spec)
        extra, make_step, hybrid = self._dist_step_parts(program)

        def local_fn(state, extra):
            return _run_batched_loop(make_step(extra), program.max_steps,
                                     state, q)

        sharded = jax.shard_map(
            local_fn, mesh=self.mesh,
            in_specs=(jax.tree.map(lambda _: spec, state),
                      jax.tree.map(lambda _: extra_spec, extra)),
            out_specs=(jax.tree.map(lambda _: spec, state), P()),
            check_vma=False)
        state = jax.device_put(state, sharding)
        if not hybrid:
            ex_shard = jax.sharding.NamedSharding(self.mesh, extra_spec)
            extra = jax.tree.map(lambda x: jax.device_put(x, ex_shard),
                                 extra)
        return jax.jit(sharded)(state, extra)

    def _chunk_call(self, program: VertexProgram, chunk: int,
                    state: BatchedState, step: Array, fin: Array,
                    steps_q: Array, poison=None):
        """Sharded chunk window for ``run_batched_chunked``.

        The scalar step / replicated fin / steps_q / poison carry rides
        through ``P()`` specs; the jitted shard_map closure is cached per
        (program, chunk, shapes) — cleared on rebind — so chunks and
        restart-rebuilt engines reuse one compile.  Every exchange inside
        the window is checksummed (``_checked_dist_exchange`` / the tagged
        hybrid compact exchange); the psum'd mismatch count returns as the
        5th element.
        """
        if poison is None:
            poison = jnp.float32(0.0)
        if self.dg is not None:
            self._sync_dynamic()
        self._validate_state(state)
        chaos.visit(
            "worker.chunk", step=int(step),
            shards=tuple(range(self.mesh.shape[self.axis])))
        spec = P(None, self.axis)
        extra_spec = P(self.axis)
        sharding = jax.sharding.NamedSharding(self.mesh, spec)
        guard = self._guard
        extra, make_step, hybrid = self._dist_step_parts(program,
                                                         guard=guard)

        def sig(tree):
            return tuple(
                (jax.tree_util.keystr(p), tuple(x.shape))
                for p, x in jax.tree_util.tree_leaves_with_path(tree))

        key = (program, chunk, sig(state), sig(extra))
        jitted = self._chunk_jits.get(key)
        if jitted is None:
            mesh_axis = self.axis

            def local_fn(state, extra, step, fin, steps_q, poison):
                guard.arm(poison)
                st, stp, fn, sq, bad = _run_chunked_loop_guarded(
                    make_step(extra), guard, chunk, program.max_steps,
                    state, step, fin, steps_q)
                # Each shard only sees mismatches on payload it received;
                # psum so the replicated out-spec holds the global count.
                return st, stp, fn, sq, jax.lax.psum(bad, mesh_axis)

            sharded = jax.shard_map(
                local_fn, mesh=self.mesh,
                in_specs=(jax.tree.map(lambda _: spec, state),
                          jax.tree.map(lambda _: extra_spec, extra),
                          P(), P(), P(), P()),
                out_specs=(jax.tree.map(lambda _: spec, state),
                           P(), P(), P(), P()),
                check_vma=False)
            jitted = jax.jit(sharded)
            self._chunk_jits[key] = jitted
        state = jax.device_put(state, sharding)
        if not hybrid:
            ex_shard = jax.sharding.NamedSharding(self.mesh, extra_spec)
            extra = jax.tree.map(lambda x: jax.device_put(x, ex_shard),
                                 extra)
        return jitted(state, extra, jnp.int32(step), fin, steps_q,
                      jnp.float32(poison))

    def superstep(self, program: VertexProgram) -> Callable:
        """One jitted distributed superstep ``f(state, step) -> (state,
        finished)`` — the benchmarking hook (state is device_put on entry;
        unbatched contract, runs as a Q=1 batch internally)."""
        if self.dg is not None:
            self._sync_dynamic()
        spec = P(None, self.axis)
        extra_spec = P(self.axis)
        sharding = jax.sharding.NamedSharding(self.mesh, spec)
        extra, make_step, hybrid = self._dist_step_parts(program)
        if not hybrid:
            ex_shard = jax.sharding.NamedSharding(self.mesh, extra_spec)
            extra = jax.tree.map(lambda x: jax.device_put(x, ex_shard),
                                 extra)

        def local_fn(state, extra, step):
            return make_step(extra)(state, step)

        jitted = {}

        def fn(state, step):
            state = batch_state(state)
            self._validate_state(state)
            key = jax.tree_util.tree_structure(state)
            if key not in jitted:
                sharded = jax.shard_map(
                    local_fn, mesh=self.mesh,
                    in_specs=(jax.tree.map(lambda _: spec, state),
                              jax.tree.map(lambda _: extra_spec, extra),
                              P()),
                    out_specs=(jax.tree.map(lambda _: spec, state), P()),
                    check_vma=False)
                jitted[key] = jax.jit(sharded)
            state = jax.device_put(state, sharding)
            out, fin = jitted[key](state, extra, step)
            return unbatch_state(out), fin[0]

        return fn
