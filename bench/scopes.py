"""Which part of a model each operation of a compiled step belongs to.

The program wraps parts of a layer in ``jax.named_scope`` (``mla``,
``moe``); the compiled module's HLO text keeps the scope in each
instruction's ``metadata={op_name="..."}``.  A chip trace names each
operation by its instruction (``fusion.12``) and carries no ``op_name``, so
the benchmark reads the step's HLO once, at set-up, into a map from
instruction to scope, and a per-layer metric sums the traced time of the
operations that the map assigns to a scope.
"""

from __future__ import annotations

import re

SCOPES = ("mla", "moe")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_ATTR_REF = re.compile(r"\w+=%[\w.\-]+")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")


def _scope(op_name: str) -> str | None:
    parts = op_name.split("/")
    for scope in SCOPES:
        if scope in parts:
            return scope
    return None


def scope_map(hlo_text: str) -> dict:
    """``{instruction: scope}`` for every instruction whose ``op_name``
    names a scope; for a fusion whose own ``op_name`` does not, the scope
    its fused computation's instructions name most often; for an
    instruction the chip compiler made itself (an ``op_name`` that is no
    path of the program, as ``ragged-dot-none`` for a ``ragged_dot``), the
    one scope its operands have, if they have exactly one."""
    own, calls, members, operands, made = {}, {}, {}, {}, set()
    computation = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and " = " not in line:
            computation = m.group(1)
            members[computation] = []
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = _scope(op.group(1)) if op else None
        if op is None or "/" not in op.group(1):
            made.add(name)
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        rhs = _ATTR_REF.sub("", line.split(" = ", 1)[1].split(", metadata=")[0])
        operands[name] = _OPERAND.findall(rhs)
        if computation is not None:
            members[computation].append(name)
    out = {}
    for name, scope in own.items():  # operands come before their users
        if scope is None and name in calls:
            found = [own.get(i) for i in members.get(calls[name], [])]
            found = [s for s in found if s]
            scope = max(set(found), key=found.count) if found else None
        if scope is None and name in made:
            found = {out[i] for i in operands[name] if i in out}
            scope = found.pop() if len(found) == 1 else None
        if scope:
            out[name] = scope
    return out


def step_seconds(run, scope: str) -> tuple[int, float] | None:
    """(calls of the step executable, traced device seconds of its
    operations in ``scope``), or None where the run has no map or no call."""
    scopes = run.counters.get("scopes")
    module = run.counters.get("step_module")
    if not scopes or not module:
        return None
    runs, _ = run.trace.module(module)
    if not runs:
        return None
    secs = 0.0
    for key, t in run.trace.ops.items():
        where, _, instr = key.partition(":")
        if (where == module or where.startswith(module + ".")) and scopes.get(instr) == scope:
            secs += t
    return runs, secs
