"""Graph generators of the benchmark's configurations, on the device.

A configuration's graph is one instance, drawn from the ``structure_seed``
its file states, so every run does the same work; a run's ``--seed``
renames its vertices by a seeded bijection, so every seed hands the
program another input (other ids, partitions and layouts) of that work.

Tuples are drawn with ``jax.random`` from the run's seed, on the device,
in chunks of ``CHUNK_EDGES``, so the generator's device memory is the
tuples themselves (8 bytes each, under the engine's resident graph) and a
chunk's draws.  On a cell of several chips each chip draws its own run of
chunks (``chunk_shares``); chunk ``i`` is keyed by its index alone, so the
graph is the same to the bit whatever the chip count, and no chip holds
more than ``ceil(chunks / chips)`` chunks of tuples.  On the host they
become the CSR arrays of ``core.graph.CSRGraph`` in the canonical order
``from_edge_list`` gives, by one sort of packed 64-bit keys instead of a
lexsort.  (A device sort of the 33.5 M entries of Graph500 scale 20 needs
536 MB, as much as the engine's own working set.)

- ``kronecker``: the Graph500 v3 Kronecker generator
  (``kronecker_generator.m`` of the reference code): per edge and per bit
  level, quadrant A, B, C or D; vertex labels are then scrambled by the
  reference code's seeded bijection (``scramble``).  ``undirected`` stores
  every tuple both ways.
- ``uniform``: TOTEM's UNIFORM graphs (arXiv:1312.3018, Table 2), directed
  Erdős–Rényi with ``edge_factor * 2**scale`` edges, endpoints uniform.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CHUNK_EDGES = 1 << 19
# Low 32 bits of the odd multipliers of Graph500's ``scramble``.
SCRAMBLE_MUL = (0x11493211, 0x02C843A5)


@dataclasses.dataclass
class GeneratedGraph:
    """Host CSR arrays of a generated graph, plus the input tuple count."""

    row_ptr: np.ndarray       # int64 [n + 1]
    col: np.ndarray           # int32 [stored entries]
    num_tuples: int           # generated tuples (stored once or twice)
    undirected: bool
    structure_seed: int = 0
    ids: Optional[np.ndarray] = None   # the run's id of each structure vertex

    @property
    def num_vertices(self) -> int:
        return len(self.row_ptr) - 1

    @property
    def num_edges(self) -> int:
        return len(self.col)

    def out_degrees(self) -> np.ndarray:
        return np.diff(self.row_ptr)

    def edge_sources(self) -> np.ndarray:
        return np.repeat(np.arange(self.num_vertices, dtype=np.int32),
                         self.out_degrees())

    def distinct_edges(self) -> int:
        """Stored entries with duplicates of one (src, dst) counted once."""
        if not self.num_edges:
            return 0
        src = self.edge_sources()
        new = np.ones(self.num_edges, dtype=bool)
        new[1:] = (src[1:] != src[:-1]) | (self.col[1:] != self.col[:-1])
        return int(new.sum())


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``jax.random.key`` keeps
    only the low 32 bits of a larger one)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _kronecker_chunk(key, scale: int, chunk: int, a: float, b: float,
                     c: float):
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    u = jax.random.uniform(key, (scale, 2, chunk))
    ii = u[:, 0] > ab
    jj = u[:, 1] > jnp.where(ii, c_norm, a_norm)
    weight = (jnp.int32(1) << jnp.arange(scale, dtype=jnp.int32))[:, None]
    src = jnp.sum(jnp.where(ii, weight, 0), axis=0, dtype=jnp.int32)
    dst = jnp.sum(jnp.where(jj, weight, 0), axis=0, dtype=jnp.int32)
    return src, dst


def _bit_reverse(v, bits: int):
    """The low ``bits`` bits of the uint32 array ``v``, in reverse order."""
    v = ((v >> 1) & 0x55555555) | ((v & 0x55555555) << 1)
    v = ((v >> 2) & 0x33333333) | ((v & 0x33333333) << 2)
    v = ((v >> 4) & 0x0F0F0F0F) | ((v & 0x0F0F0F0F) << 4)
    v = ((v >> 8) & 0x00FF00FF) | ((v & 0x00FF00FF) << 8)
    v = (v >> 16) | (v << 16)
    return v >> (32 - bits)


def scramble(v, scale: int, val0, val1):
    """Graph500's vertex-label scramble (``scramble`` in the reference
    code's ``generator/graph_generator.c``) in 32-bit arithmetic: a
    bijection of ``[0, 2**scale)`` keyed by the uint32s ``val0``, ``val1``.
    Each step keeps the low ``scale`` bits a bijection: an add and a
    multiply by an odd number modulo 2**32, then those bits reversed.
    Elementwise, so it compiles in no time, where a sorted permutation of
    2**20 labels takes the chip's compiler tens of seconds."""
    v = v.astype(jnp.uint32)
    v = (v + val0 + val1) * (val0 | SCRAMBLE_MUL[0])
    v = _bit_reverse(v, scale)
    v = v * (val1 | SCRAMBLE_MUL[1])
    return _bit_reverse(v, scale).astype(jnp.int32)


def _uniform_chunk(key, scale: int, chunk: int):
    k_src, k_dst = jax.random.split(key)
    n = 1 << scale
    return (jax.random.randint(k_src, (chunk,), 0, n, jnp.int32),
            jax.random.randint(k_dst, (chunk,), 0, n, jnp.int32))


@functools.partial(jax.jit, static_argnames=("kind", "scale", "num_tuples",
                                             "chunk"))
def _tuples(key, labels_key, first, a, b, c, *, kind: str, scale: int,
            num_tuples: int, chunk: int):
    """``num_tuples`` generated ``(src, dst)`` tuples of the structure drawn
    from ``key``, chunks ``first`` onwards, in the ids ``labels_key`` gives,
    and those ids of the structure's vertices.  One chunk is drawn per loop
    step and written in place, so only a chunk's random draws are live
    beside the result."""
    k_edges, k_scramble = jax.random.split(key)
    val0, val1 = jax.random.bits(k_scramble, (2,), jnp.uint32)
    lab0, lab1 = jax.random.bits(labels_key, (2,), jnp.uint32)

    def body(i, carry):
        src, dst = carry
        k = jax.random.fold_in(k_edges, first + i)
        if kind == "kronecker":
            s, d = _kronecker_chunk(k, scale, chunk, a, b, c)
            s = scramble(s, scale, val0, val1)
            d = scramble(d, scale, val0, val1)
        else:
            s, d = _uniform_chunk(k, scale, chunk)
        s = scramble(s, scale, lab0, lab1)
        d = scramble(d, scale, lab0, lab1)
        return (jax.lax.dynamic_update_slice(src, s, (i * chunk,)),
                jax.lax.dynamic_update_slice(dst, d, (i * chunk,)))

    empty = jnp.zeros((num_tuples,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, num_tuples // chunk, body, (empty, empty))
    ids = scramble(jnp.arange(1 << scale, dtype=jnp.int32), scale, lab0,
                   lab1)
    return src, dst, ids


def chunk_shares(chunks: int, parts: int) -> List[Tuple[int, int]]:
    """``(first, count)`` of the chunks each of ``parts`` devices draws: runs
    of ``ceil(chunks / parts)`` in order, the last ones shorter or empty."""
    per = -(-chunks // parts)
    return [(min(j * per, chunks), max(0, min(per, chunks - j * per)))
            for j in range(parts)]


def generate(spec: dict, seed: int, devices=None) -> GeneratedGraph:
    """The graph a configuration's ``graph`` block describes, its vertices
    named by ``seed``, drawn on ``devices`` (default: JAX's default device).

    ``spec`` keys: ``generator`` (``kronecker`` or ``uniform``),
    ``structure_seed``, ``scale``, ``edge_factor``, ``undirected``, and for
    ``kronecker`` the quadrant probabilities ``a``, ``b``, ``c`` (``d`` is
    the rest).
    """
    scale = int(spec["scale"])
    n = 1 << scale
    num_tuples = int(spec["edge_factor"]) * n
    kind = spec["generator"]
    if kind not in ("kronecker", "uniform"):
        raise ValueError(f"unknown generator {kind!r}")
    chunk = min(CHUNK_EDGES, num_tuples)
    if num_tuples % chunk:
        raise ValueError(f"{num_tuples} tuples are not a whole number of "
                         f"{chunk}-tuple chunks")
    structure_seed = int(spec["structure_seed"])
    keys = (seed_key(structure_seed), seed_key(seed))
    abc = (float(spec.get("a", 0.0)), float(spec.get("b", 0.0)),
           float(spec.get("c", 0.0)))
    devices = list(devices or [None])
    shares = chunk_shares(num_tuples // chunk, len(devices))
    # Every device's program is dispatched before any result is read, so
    # the devices draw at once.
    drawn = [_tuples(*jax.device_put(keys, device), first, *abc, kind=kind,
                     scale=scale, num_tuples=count * chunk, chunk=chunk)
             for device, (first, count) in zip(devices, shares) if count]
    src = np.concatenate([np.asarray(s) for s, _, _ in drawn])
    dst = np.concatenate([np.asarray(d) for _, d, _ in drawn])
    src, dst = src.astype(np.int64), dst.astype(np.int64)
    ids = drawn[0][2]
    del drawn
    undirected = bool(spec["undirected"])
    if undirected:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    # One sort of (src, dst) packed into an int64 key: the canonical order
    # in a second or so, where a lexsort takes many.
    packed = np.sort((src << scale) | dst)
    del src, dst
    row_ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(packed >> scale, minlength=n), out=row_ptr[1:])
    col = (packed & (n - 1)).astype(np.int32)
    return GeneratedGraph(row_ptr=row_ptr, col=col,
                          num_tuples=num_tuples, undirected=undirected,
                          structure_seed=structure_seed, ids=np.asarray(ids))
