"""Named scopes of the train step, and the table from a compiled program's
instructions to them.

The step's layers run under ``jax.named_scope(<name>)`` with a name from
``SCOPES``.  XLA keeps the scope in each instruction's ``op_name``
metadata (``jit(train_step)/jvp(attn)/dot_general`` forward,
``transpose(jvp(...))/.../attn/...`` backward), but a device trace names
an op by its HLO instruction alone (``fusion.309``).  So the program keeps
what it compiled: ``note_program`` stores the jitted function, its
abstract arguments and mesh, and ``table`` lowers, compiles and parses it
on first request, giving instruction name -> scope.  Nothing is compiled
until someone asks.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional

import jax

SCOPES = ("embed", "attn", "mla", "attn_core", "mlp", "moe_route",
          "moe_experts", "ssm_block", "ssm_scan", "head_loss", "optimizer")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LOOP_BODY = re.compile(r"\b(?:body|condition)=%?([\w.\-]+)")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost name of ``SCOPES`` on an ``op_name`` path, looking
    through transform wrappers; None where no scope matches."""
    found = None
    for part in op_name.split("/"):
        # ``transpose(jvp(attn))`` -> ``attn``
        part = part.rstrip(")").rsplit("(", 1)[-1]
        if part in SCOPES:
            found = part
    return found


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope for every instruction of a module's text
    that has one.  An instruction whose ``op_name`` names no scope (such
    as a copy the compiler added) takes the scope of the loop that runs
    its computation."""
    own: Dict[str, Optional[str]] = {}
    comp_of: Dict[str, Optional[str]] = {}
    loop_of: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ") and line.rstrip().endswith("{"):
            # a computation's header: ``[ENTRY ]%name (params) -> type {``
            comp = line.removeprefix("ENTRY ").split()[0].lstrip("%")
            continue
        instr = _INSTR.match(line)
        if not instr:
            continue
        name = instr.group(1)
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else None
        comp_of[name] = comp
        for called in _LOOP_BODY.findall(line):
            loop_of[called] = name

    def scope(name):
        while own[name] is None and comp_of[name] in loop_of:
            name = loop_of[comp_of[name]]
        return own[name]

    return {n: s for n in own if (s := scope(n)) is not None}


_PROGRAMS: Dict[str, tuple] = {}
_TABLES: Dict[str, Dict[str, str]] = {}


def note_program(name: str, jitted, abstract_args: tuple, mesh) -> None:
    """Remembers how to compile the program ``name`` (references only;
    replaces an earlier note and its table)."""
    _PROGRAMS[name] = (jitted, abstract_args, mesh)
    _TABLES.pop(name, None)


def table(name: str) -> Optional[Dict[str, str]]:
    """Instruction name -> scope of the noted program ``name``, compiled
    on the first call (from the compile cache where the program ran);
    None when no such program was noted."""
    if name not in _TABLES:
        if name not in _PROGRAMS:
            return None
        jitted, args, mesh = _PROGRAMS[name]
        with jax.set_mesh(mesh):
            text = jitted.lower(*args).compile().as_text()
        _TABLES[name] = op_scopes(text)
    return _TABLES[name]


def abstract(tree: Any) -> Any:
    """Shape, dtype and sharding of each array of ``tree``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=x.sharding), tree)
