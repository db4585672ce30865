"""Device time per phase of the federated round.

The program names each phase of its round with a device scope
``fed.<phase>`` (`repro.obs.phase`); the compiled module carries it in
each instruction's ``metadata={op_name="..."}``.  The trace names a
device operation by its instruction, so the module's text maps each
operation to a phase: the innermost ``fed.`` component of its
``op_name`` (a fusion's metadata is its root's).  What runs inside a
phase's loop or branch is that phase's.  Instructions the compiler
makes carry no ``op_name`` at all (layout copies, the copies and
fusions it splits out of the program's ops): such an instruction takes
the phase of what it fuses, else that of its users, else that of its
operands, where those agree (`hlo_phases`).  An operation with an
``op_name`` outside every phase, or none in the module, goes to the
rest.

A phase's time is the self time of its operations in the traced
window (`bench.tracing`), over the devices and the traced rounds, in
ms per round.  The rest is the busy time per round less the phases, so
the phases and the rest sum to the busy time.  Where the module names
no phase at all (a program without the scopes), every reading is None.

The module is the compiled round of the run's `harness.Program`, the
one the traced rounds ran: a reader is handed the run's
`harness.TraceContext`, which holds the cell but not the module, so
`compiled_text` finds the program by its cell.
"""
from __future__ import annotations

import gc
import re
from collections import defaultdict
from typing import Dict, List, Optional

#: the round's phases, as the program names its scopes
PHASES = ("grad", "gnb", "sophia", "wire", "combine", "rows")
#: the reading of the busy time outside every phase
REST = "other"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s(.*)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_PHASE = re.compile(r"(?:^|[/(])fed\.(" + "|".join(PHASES) + r")(?=[/)]|$)")
_REF = re.compile(r"%([\w.\-]+)")
_FUSES = re.compile(r"\bfusion\(.*\bcalls=%([\w.\-]+)")


def phase_of(op_name: str) -> Optional[str]:
    """The innermost ``fed.<phase>`` of an ``op_name``, or None."""
    found = _PHASE.findall(op_name)
    return found[-1] if found else None


def hlo_phases(hlo_text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> phase (None: no phase) of every instruction
    of a module's text.  An instruction inside a loop, call or branch
    computation runs as part of the instruction that calls it, and
    takes its phase where it names none itself.  An instruction without
    an ``op_name`` takes, in order: for a fusion, the phase of the root
    of the computation it fuses, else the one phase its fused
    instructions name; the one phase its users have (users first, so a
    chain of such instructions follows the op it feeds); the one phase
    its operands have (a relayout of a phase's result)."""
    own: Dict[str, Optional[str]] = {}
    bare, order = set(), []
    refs: Dict[str, List[str]] = {}
    fuses: Dict[str, str] = {}
    body: Dict[str, List[str]] = defaultdict(list)
    root: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and not line[0].isspace():
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        op = _OP_NAME.search(rhs)
        own[name] = phase_of(op.group(1)) if op else None
        if not op:
            bare.add(name)
        order.append(name)
        refs[name] = _REF.findall(rhs.split(" metadata=", 1)[0])
        f = _FUSES.search(rhs)
        if f:
            fuses[name] = f.group(1)
        body[comp].append(name)
        if line.lstrip().startswith("ROOT"):
            root[comp] = name
    out = dict(own)
    for name, called in fuses.items():
        if name in bare:
            inside = {own[i] for i in body[called]} - {None}
            out[name] = own.get(root.get(called)) or (
                inside.pop() if len(inside) == 1 else None)
    caller = {c: name for name in order if name not in fuses
              for c in refs[name] if c in body}
    changed = True
    while changed:
        changed = False
        for c, name in caller.items():
            for i in body[c]:
                if out[i] is None and out[name] is not None:
                    out[i] = out[name]
                    changed = True
    users: Dict[str, set] = defaultdict(set)
    for name in order:
        for r in refs[name]:
            users[r].add(name)
    for name in reversed(order):
        if name in bare and out[name] is None:
            found = {out[u] for u in users[name]} - {None}
            if len(found) == 1:
                out[name] = found.pop()
    for name in order:
        if name in bare and out[name] is None:
            found = {out[r] for r in refs[name] if r in out} - {None}
            if len(found) == 1:
                out[name] = found.pop()
    return out


def phase_ns(red, mapping: Dict[str, Optional[str]]) -> Optional[Dict]:
    """Self time (ns, summed over devices) of each phase, and of the
    rest as busy time less the phases; None where ``mapping`` names no
    phase."""
    if red is None or not any(mapping.values()):
        return None
    out = dict.fromkeys(PHASES, 0.0)
    for name, ns in red.op_ns.items():
        ph = mapping.get(name)
        if ph is not None:
            out[ph] += ns
    out[REST] = red.busy_ns * red.devices - sum(out.values())
    return out


def compiled_text(ctx) -> Optional[str]:
    """The text of the compiled round that the traced rounds of
    ``ctx``'s run ran; None where the run has none."""
    from bench import harness
    for obj in gc.get_objects():
        if (isinstance(obj, harness.Program) and obj.cell is ctx.cell
                and obj.compiled is not None):
            return obj.compiled.as_text()
    return None


def phase_ms(ctx, phase: str) -> Optional[float]:
    """Device self time of ``phase`` (one of `PHASES`, or `REST`) per
    traced round, in ms; None where the trace, the module or the
    scopes are missing.  The phases are read once per context and kept
    on it for the other readers."""
    if not hasattr(ctx, "_phase_ns"):
        text = compiled_text(ctx)
        ctx._phase_ns = None if text is None else phase_ns(
            ctx.reduction, hlo_phases(text))
    per = ctx._phase_ns
    if per is None:
        return None
    return per[phase] / ctx.reduction.devices / ctx.rounds * 1e-6


_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def without_metadata(hlo_text: str) -> str:
    """A module's text without what names source and scopes: each
    instruction's ``metadata={...}`` and the tables of files, functions
    and stack frames that metadata points into."""
    out, table = [], False
    for line in hlo_text.splitlines():
        if line in _TABLES:
            table = True
        if table:
            table = bool(line.strip())
            continue
        out.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return "\n".join(out)
