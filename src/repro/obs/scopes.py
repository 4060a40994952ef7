"""The naming contract between the round program and its device trace
(DESIGN.md §12).

Every piece of device work in the round program runs under a
``jax.named_scope``; the names are HLO metadata only and change no array:

  * ``hop.<name>`` — one per ``RoundProgram`` hop (``hop.local_update``,
    ``hop.wire``, ``hop.server_opt``, ...), plus ``hop.data`` (the batch
    sampled inside ``RoundRunner``'s scan) and ``hop.eval``
    (``metrics_fn``);
  * ``stage.<base>`` — one per wire stage, named by the stage's name without
    arguments or backend suffix (``stage.topk``, ``stage.qsgd``,
    ``stage.secagg``, ...), plus ``stage.ef`` (error feedback's own
    arithmetic) and ``stage.aggregate`` (the weighted mean of decoded rows).
    Stages nest: ``stage.ef/stage.topk``.

Host spans that should line up with the device trace open a
``jax.profiler.TraceAnnotation`` named ``repro.<kind>`` (``repro.chunk``
around every compiled chunk call).

:func:`scope_table` reads the contract back out of a compiled HLO text
(``compiled.as_text()``): for every instruction that runs as a device
operation, the outermost hop and the innermost stage it ran under.  A
profiler trace names its operations by those instruction names, so the
table attributes device time to hops and stages.  Stdlib-only, like the
rest of the package's host side.
"""
from __future__ import annotations

import re

HOP = "hop."
STAGE = "stage."
ANNOTATION = "repro."

_BASE = re.compile(r"[A-Za-z_]+")
_HOP_IN = re.compile(re.escape(HOP) + r"(\w+)")
_STAGE_IN = re.compile(re.escape(STAGE) + r"(\w+)")
_COMP = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
# computations whose instructions run as device operations of their own:
# loop bodies and conditions, conditional branches and (for a call) its
# target; a fusion's, a reduction's or a sort's computations do not
_LOOP = re.compile(r"\b(?:body|condition|true_computation"
                   r"|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def stage(name: str) -> str:
    """The scope of a wire stage named ``name``: ``"topk0.01@kernel"`` ->
    ``"stage.topk"``."""
    m = _BASE.match(name)
    return STAGE + (m.group(0) if m else name)


def scopes_of(op_name: str) -> tuple:
    """``(hop, stage)`` of one ``op_name``: the outermost ``hop.*`` and the
    innermost ``stage.*`` in it, without their prefixes; None where there
    is none.  Transforms wrap scopes (``vmap(stage.ef)/stage.topk``), so
    the names are found wherever they stand in the path."""
    hops = _HOP_IN.findall(op_name or "")
    stages = _STAGE_IN.findall(op_name or "")
    return (hops[0] if hops else None, stages[-1] if stages else None)


def _parse(hlo_text: str):
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if m:
            comps[cur].append((m.group(2), bool(m.group(1)), m.group(3)))
    return comps, entry


def scope_table(hlo_text: str) -> dict:
    """``{instruction: (hop, stage)}`` for every instruction of a compiled
    HLO text that runs as a device operation (those of the entry
    computation and of the loops, branches and calls it reaches; not those
    inside fusions or reductions).

    An instruction is named by its own ``op_name``; a fusion with none, by
    its fused root's.  One whose name holds no hop — a constant that XLA
    shared between hops, an operation a compiler pass made without metadata
    — is work done for its consumers, and takes the scopes of its first
    user that has a hop; an asynchronous copy (``copy-start`` /
    ``copy-done``) with none moves the output of its operand, and takes
    that operand's scopes, or, where the operand has none either (a value
    carried by the loop), its consumers'.  The operations of a loop or call
    the compiler made (a relayout of a large array done in slices) take,
    where they have no hop, the scopes of the instruction that runs them.
    What is left with no hop is the scan's own plumbing: its carry, its
    stacked outputs, its counter."""
    comps, entry = _parse(hlo_text or "")
    if entry is None:
        return {}
    roots = {}
    for cname, instrs in comps.items():
        for _, is_root, rest in instrs:
            if is_root:
                m = _OP_NAME.search(rest)
                roots[cname] = m.group(1) if m else None
    table, seen, todo, caller = {}, {entry}, [entry], {}
    while todo:
        cname = todo.pop()
        for name, _, rest in comps.get(cname, ()):
            m = _OP_NAME.search(rest)
            op_name = m.group(1) if m else None
            called = _CALLS.search(rest)
            if op_name is None and called:
                op_name = roots.get(called.group(1))
            table[name] = scopes_of(op_name)
            targets = _LOOP.findall(rest)
            for group in _BRANCHES.findall(rest):
                targets += [t.strip().lstrip("%") for t in group.split(",")]
            op = _OPCODE.search(rest)
            if op and op.group(1) == "call":
                targets += _TO_APPLY.findall(rest)
            for t in targets:
                if t in comps and t not in seen:
                    seen.add(t)
                    todo.append(t)
                    caller[t] = name
    _from_users(comps, seen, table)
    for cname, by in caller.items():           # callers before callees
        if table[by][0] is not None:
            for name, _, _ in comps[cname]:
                if table[name][0] is None:
                    table[name] = table[by]
    return table


def _from_users(comps, executed, table):
    for cname in executed:
        instrs = comps[cname]
        names = {n for n, _, _ in instrs}
        users, copies = {}, set()
        for name, _, rest in instrs:          # in schedule order
            op = _OPCODE.search(rest)
            if op and op.group(1).endswith(("-start", "-done")):
                copies.add(name)
            for ref in _REF.findall(rest.split(", metadata=", 1)[0]):
                if ref in names and ref != name:
                    users.setdefault(ref, []).append(name)
        _take_users(instrs, users, table, lambda n: n not in copies)
        for name, _, rest in instrs:          # producers before copies
            if table[name][0] is None and name in copies:
                refs = [r for r in _REF.findall(rest) if r in names]
                if refs:
                    table[name] = table[refs[0]]
        # a copy of an operand that has no hop (a loop-carried value)
        # brings it in for its consumers
        _take_users(instrs, users, table, lambda n: n in copies)


def _take_users(instrs, users, table, which):
    for name, _, _ in reversed(instrs):       # users before producers
        if table[name][0] is None and which(name):
            scoped = [table[u] for u in users.get(name, ())
                      if table[u][0] is not None]
            if scoped:
                table[name] = scoped[0]
