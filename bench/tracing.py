"""Reduction of a profiler trace (``.xplane.pb``) to device time, and
of device time to the program's named scopes.

Device operations are the events of each device plane's ``XLA Ops``
line (a TPU, where an event's name is its HLO instruction as printed,
``%name = ...``, and a loop or conditional is an event around the events
of its body); where the trace has no device plane (a CPU run) they are
the host events that carry an ``hlo_op`` statistic.  Busy time is the
union of the operations' intervals; an operation's time is its self
time, less what the operations nested in it cover; the window is the
harness's ``bench.traced`` host span; each idle gap inside it is named
after the innermost ``bench.*`` host span that covers its midpoint.

Scopes.  The program names parts of its round with device scopes
(``jax.named_scope``; the round's phases are ``fed.<phase>``,
`repro.obs.phase`), which the compiled module carries in each
instruction's ``metadata={op_name="..."}``.  The trace names a device
operation by its instruction, so the module's text maps each operation
to a scope: the innermost of a given set of scopes in its ``op_name``
(a fusion's metadata is its root's).  What runs inside a scope's loop or
branch is that scope's.  Instructions the compiler makes carry no
``op_name`` at all (layout copies, the copies and fusions it splits out
of the program's ops): such an instruction takes the scope of what it
fuses, else that of its users, else that of its operands, where those
agree (`hlo_phases`).  An operation outside every scope of the set goes
to the rest, which is the busy time less the scopes (`phase_ns`).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.traced"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"

#: the round's phases, as the program names its scopes
PHASES = ("grad", "gnb", "sophia", "wire", "combine", "rows")
#: label -> device scope of each phase
PHASE_SCOPES = {p: "fed." + p for p in PHASES}
#: the reading of the busy time outside every scope of a set
REST = "other"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Reduction:
    """Busy and idle time of the traced window, in nanoseconds."""
    window: Interval
    busy_ns: float                      # mean over devices
    op_ns: Dict[str, float]             # per op name: self time, summed
    #                                     over its events and devices
    op_count: Dict[str, int]
    gaps: List[Tuple[str, float]]       # (host span, ns), longest first
    devices: int

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def newest_xplane(directory: str) -> Optional[str]:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def op_name(name: str) -> str:
    """The HLO instruction name of an operation event."""
    if name.startswith("%"):
        return name[1:].split(" = ", 1)[0].split(" ", 1)[0]
    return name


def device_ops(pd) -> List[List[Tuple[str, float, float]]]:
    """Per device, its operations as (name, start_ns, end_ns)."""
    per_dev = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                per_dev.append([(op_name(e.name), e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events])
    if per_dev:
        return per_dev
    ops = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if "hlo_op" in _stats(e):
                    ops.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return [ops] if ops else []


def host_spans(pd) -> List[Tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def union(intervals: List[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def self_times(ops, lo: float, hi: float) -> Dict[str, List[float]]:
    """name -> self time (ns, clipped to [lo, hi]) of each of its events
    that overlaps the window: its interval less its nested events'."""
    out: Dict[str, List[float]] = defaultdict(list)
    stack: List[List] = []          # [name, end, self time]

    def close(entry):
        out[entry[0]].append(entry[2])

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        s_c, e_c = max(s, lo), min(e, hi)
        own = max(e_c - s_c, 0.0)
        if stack:
            stack[-1][2] -= own
        if e > lo and s < hi:
            stack.append([name, e, own])
        else:
            stack.append([None, e, 0.0])
    while stack:
        close(stack.pop())
    out.pop(None, None)
    return out


def _label(spans, t: float) -> str:
    best = None
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= t <= e and (
                best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "(no host span)"


def reduce(pd) -> Optional[Reduction]:
    """None where the trace holds no device operation or no window."""
    per_dev = device_ops(pd)
    spans = host_spans(pd)
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not per_dev or not win:
        return None
    lo, hi = win[0]
    busy, op_ns, op_n = [], defaultdict(float), defaultdict(int)
    gaps = defaultdict(float)
    for ops in per_dev:
        merged = union([(s, e) for _, s, e in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for name, times in self_times(ops, lo, hi).items():
            op_ns[name] += sum(times)
            op_n[name] += len(times)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_label(spans, (a + b) / 2)] += b - a
    return Reduction(window=(lo, hi), busy_ns=sum(busy) / len(busy),
                     op_ns=dict(op_ns), op_count=dict(op_n),
                     gaps=sorted(gaps.items(), key=lambda kv: -kv[1]),
                     devices=len(per_dev))


# -------------------------------------------------------------- scopes
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s(.*)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_REF = re.compile(r"%([\w.\-]+)")
_FUSES = re.compile(r"\bfusion\(.*\bcalls=%([\w.\-]+)")


def _scope_finder(scopes: Dict[str, str]):
    """op_name -> the label of its innermost scope of ``scopes`` (label
    -> scope name), or None."""
    label = {v: k for k, v in scopes.items()}
    pattern = re.compile(r"(?:^|[/(])(" + "|".join(
        re.escape(v) for v in scopes.values()) + r")(?=[/)]|$)")

    def find(op_name: str) -> Optional[str]:
        found = pattern.findall(op_name)
        return label[found[-1]] if found else None
    return find


def phase_of(op_name: str, scopes: Dict[str, str] = PHASE_SCOPES
             ) -> Optional[str]:
    """The label of the innermost of ``scopes`` in an ``op_name``."""
    return _scope_finder(scopes)(op_name)


def hlo_phases(hlo_text: str, scopes: Dict[str, str] = PHASE_SCOPES
               ) -> Dict[str, Optional[str]]:
    """Instruction name -> label of its scope (None: none of
    ``scopes``) of every instruction of a module's text.  An instruction
    inside a loop, call or branch computation runs as part of the
    instruction that calls it, and takes its scope where it names none
    itself.  An instruction without an ``op_name`` takes, in order: for
    a fusion, the scope of the root of the computation it fuses, else
    the one scope its fused instructions name; the one scope its users
    have (users first, so a chain of such instructions follows the op
    it feeds); the one scope its operands have (a relayout of a scope's
    result)."""
    scope_of = _scope_finder(scopes)
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
        own[name] = scope_of(op.group(1)) if op else None
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


def phase_ns(red: Optional[Reduction], mapping: Dict[str, Optional[str]],
             labels=PHASES) -> Optional[Dict[str, float]]:
    """Self time (ns, summed over devices) of each of ``labels``, and of
    the rest (`REST`) as busy time less those; None where ``mapping``
    names no scope."""
    if red is None or not any(mapping.values()):
        return None
    out = dict.fromkeys(labels, 0.0)
    for name, ns in red.op_ns.items():
        ph = mapping.get(name)
        if ph is not None:
            out[ph] += ns
    out[REST] = red.busy_ns * red.devices - sum(out.values())
    return out


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


def load(directory: str):
    from jax.profiler import ProfileData
    path = newest_xplane(directory)
    return None if path is None else ProfileData.from_file(path)


def summary(pd, events: int = 5) -> str:
    """Planes, lines, event counts and the first events of each line,
    to read a trace by hand before writing code against it."""
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for e in evs[:events]:
                out.append(f"    {e.name!r} start_ns={e.start_ns} "
                           f"dur_ns={e.duration_ns} {_stats(e)}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(summary(load(sys.argv[1]),
                  int(sys.argv[2]) if len(sys.argv) > 2 else 5))
