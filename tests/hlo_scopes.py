"""Helpers of the scope tests: compile an engine's run loop and read the
``bsp.*`` phase of every instruction of the optimized HLO.  (Not a
``conftest.py``: test files import these by module name.)"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import obs

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(ROOT )?%(\S+) = .*? ([a-z][\w\-]*)\(")
# Instructions that move data by index, and kernel calls: each must carry a
# phase, and so must a fusion rooted at one.
INDEXED = ("gather", "scatter", "sort", "custom-call")


def compiled_text(engine, program, state, num_steps: Optional[int] = None,
                  place: Callable = lambda tree: tree) -> str:
    """Optimized HLO of the loop ``engine.execute(program, state[,
    num_steps=])`` runs, its operands passed through ``place`` (e.g. to
    shapes on a described chip)."""
    if engine._direction_enabled(program):      # as execute() does
        if not engine._uses_hybrid(program):
            engine._ensure_direction_edges()
        q = state[next(iter(state))].shape[0]
        parts = engine.pg.num_parts
        state = dict(state, _dopt_dir=jnp.full((q, parts), -1, jnp.int32),
                     _dopt_edges=jnp.zeros((q, parts), jnp.int32),
                     _dopt_switch=jnp.zeros((q, parts), jnp.int32))
    edges = engine._edges_or_none(program)
    edges = None if edges is None else place(edges)
    cls = type(engine)
    if num_steps is None:
        lowered = cls._run_batched.lower(engine, program, edges,
                                         place(state))
    else:
        lowered = cls._run_fixed_batched.lower(engine, program, num_steps,
                                               edges, place(state))
    return lowered.compile().as_text()


def instructions(text: str) -> List[Dict[str, str]]:
    """Every instruction: ``name``, ``op`` (its opcode, or the opcode of
    its fused computation's root for a fusion, as ``fusion:<root>``),
    ``op_name`` and ``target`` (a custom call's)."""
    roots: Dict[str, str] = {}
    rows = []
    computation = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        is_root, name, op = m.groups()
        if is_root:
            roots[computation] = op
        meta = re.search(r'op_name="([^"]*)"', line)
        calls = re.search(r"calls=%([\w.\-]+)", line)
        target = re.search(r'custom_call_target="([^"]*)"', line)
        rows.append({"name": name, "op": op,
                     "calls": calls.group(1) if calls else "",
                     "op_name": meta.group(1) if meta else "",
                     "target": target.group(1) if target else ""})
    for row in rows:
        if row["op"] == "fusion":
            row["op"] = "fusion:" + roots.get(row.pop("calls"), "")
        else:
            row.pop("calls")
    return rows


def phase_of(op_name: str) -> Optional[str]:
    """The last component of ``op_name`` that is a phase."""
    found = [c for c in op_name.split("/") if c in obs.PHASES]
    return found[-1] if found else None


def unphased(rows) -> List[Dict[str, str]]:
    """Indexed instructions, and fusions rooted at one, with no phase."""
    return [r for r in rows
            if r["op"].split(":")[-1] in INDEXED
            and phase_of(r["op_name"]) is None]
