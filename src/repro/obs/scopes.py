"""Which named scope each operation of a compiled program belongs to.

``jax.named_scope`` names reach a compiled program only as HLO metadata
(``metadata={op_name="jit(f)/shard_map/s0.map/add"}``); a profiler's
device events carry just the HLO instruction.  :func:`op_scopes` reads
the compiled program's text once and maps every instruction name to its
scope, so device time per op can be summed per scope.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import Dict, List, Optional, Pattern, Tuple

#: Scope of an instruction whose metadata names none of the scopes asked for.
UNSCOPED = "unscoped"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%([^\s=]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition)=%([^\s,)}]+)")
_BRANCHES = re.compile(r"\b(?:branch_computations|called_computations)"
                       r"=\{([^}]*)\}")
_OPERAND = re.compile(r"%([^\s,(){}]+)")

#: name, scope, called computations, operands
_Instr = Tuple[str, Optional[str], List[str], List[str]]


def _parse(hlo_text: str, scope: Pattern[str]
           ) -> Tuple[Dict[str, List[_Instr]], Dict[str, str]]:
    """Per computation its instructions, and each computation's root."""
    comps: Dict[str, List[_Instr]] = {}
    roots: Dict[str, str] = {}
    current: Optional[str] = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                current = c.group(1)
                comps[current] = []
            continue
        if current is None:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        hit = scope.search(op.group(1)) if op else None
        callees = _CALLS.findall(line)
        for group in _BRANCHES.findall(line):
            callees += [c.strip().lstrip("%") for c in group.split(",")
                        if c.strip()]
        operands = [o for o in _OPERAND.findall(line[m.end():])
                    if o not in callees]
        comps[current].append((name, hit.group(1) if hit else None,
                               callees, operands))
        if m.group(1):
            roots[current] = name
    return comps, roots


def op_scopes(hlo_text: str, scope: Pattern[str]) -> Dict[str, str]:
    """``{HLO instruction name: scope}`` over every computation of
    ``hlo_text`` (a compiled module's ``as_text()``).

    An instruction's scope is group 1 of ``scope`` searched in its own
    ``op_name``.  Where that finds none (XLA drops the metadata of some
    ops it makes, such as a fusion's root copy or a layout copy), an
    instruction that calls computations (a fusion, a sort's comparator,
    a loop) takes the scope of the called computation's root, else the
    scope most of the called computations' instructions hold; failing
    that, the scope of its first operand that has one (a copy of a
    stage's output), else of its first user that has one of its own (a
    copy of a program input).  A fusion that spans two scopes therefore
    counts under its root's.  Instructions left without a scope map to
    :data:`UNSCOPED`.
    """
    comps, roots = _parse(hlo_text, scope)
    own = {i[0]: i[1] for body in comps.values() for i in body}
    calls = {i[0]: i[2] for body in comps.values() for i in body}
    args = {i[0]: i[3] for body in comps.values() for i in body}
    users: Dict[str, List[str]] = {}
    for name, operands in args.items():
        for operand in operands:
            users.setdefault(operand, []).append(name)
    of_comp: Dict[str, Optional[str]] = {}
    of_instr: Dict[str, Optional[str]] = {}

    def comp_scope(comp: str) -> Optional[str]:
        if comp in of_comp:
            return of_comp[comp]
        of_comp[comp] = None                    # a cycle finds nothing
        root = roots.get(comp)
        found = instr_scope(root) if root is not None else None
        if found is None:
            votes = Counter(s for s in (own_scope(i[0])
                                        for i in comps.get(comp, ()))
                            if s is not None)
            found = votes.most_common(1)[0][0] if votes else None
        of_comp[comp] = found
        return found

    def own_scope(name: str) -> Optional[str]:
        """From the instruction's own metadata or what it calls."""
        if own.get(name) is not None:
            return own[name]
        for comp in calls.get(name, ()):
            found = comp_scope(comp)
            if found is not None:
                return found
        return None

    def instr_scope(name: str) -> Optional[str]:
        if name not in of_instr:
            of_instr[name] = None               # a cycle finds nothing
            found = own_scope(name)
            for operand in args.get(name, ()) if found is None else ():
                found = instr_scope(operand)
                if found is not None:
                    break
            for user in users.get(name, ()) if found is None else ():
                found = own_scope(user)
                if found is not None:
                    break
            of_instr[name] = found
        return of_instr[name]

    return {name: instr_scope(name) or UNSCOPED for name in own}
