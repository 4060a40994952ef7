"""Device time of the traced window by the program's own scopes.

The round program names its work with ``jax.named_scope``: ``hop.<name>``
for each hop of the round, ``stage.<name>`` for each wire stage
(``repro.obs.scopes``).  ``table(hlo)`` reads those names out of the
compiled round's HLO text, by instruction; ``times(trace, ctx)`` sums the
device operations of the window by them, in ms per round.  The table is
``ctx["scopes"]`` where the context holds one; the harness's context holds
none, so it is read from the compiled text that ``harness.run_cell`` keeps
as ``hlo`` in a traced run, while the readers run inside it.

Each sum is the union of the intervals of the synchronous operations under
one name, clipped to the window, per chip and averaged over the chips.
Loops and calls enclose their bodies' operations and are left out.
Asynchronous operations (``*-start``, ``*-done``) overlap the compute and
are left out of the sums too; their time by hop is logged, to say whose
copies they are.  A program without the scopes (one from before them) gives
an empty table, and every reader of these sums then finds nothing.
"""
from __future__ import annotations

import sys

from benchlib import xtrace

ASYNC = ("-start", "-done")
TOP = 12
_LAST = []


def table(hlo) -> dict:
    try:
        from repro.obs.scopes import scope_table
    except ImportError:
        return {}
    return scope_table(hlo) if hlo else {}


def harness_hlo():
    """The compiled round's HLO text of the traced run being read: the local
    ``hlo`` of the innermost ``benchlib.harness.run_cell`` on the stack, or
    None outside one."""
    frame = sys._getframe(1)
    while frame is not None:
        if (frame.f_code.co_name == "run_cell"
                and frame.f_globals.get("__name__") == "benchlib.harness"):
            return frame.f_locals.get("hlo")
        frame = frame.f_back
    return None


def is_async(name: str) -> bool:
    return name.split(".")[0].endswith(ASYNC)


def times(trace, ctx):
    """``{"hop": {hop: ms}, "stage": {stage: ms}, "async": {hop: ms},
    "busy_ms": ms}`` per round, or None where the program names no hop.
    The hop ``None`` holds the operations with no hop scope."""
    if _LAST and _LAST[0][0] is trace and _LAST[0][1] is ctx:
        return _LAST[0][2]
    scopes = ctx["scopes"] if "scopes" in ctx else table(harness_hlo())
    if not any(hop for hop, _ in scopes.values()):
        _LAST[:] = [(trace, ctx, None)]
        return None
    lo, hi = trace["lo"], trace["hi"]
    sums = {"hop": {}, "stage": {}, "async": {}, "busy_ms": 0.0}
    op_ns = {}
    for ops in trace["ops"].values():
        by = {"hop": {}, "stage": {}, "async": {}, "busy_ms": {None: []}}
        for name, start, dur in ops:
            iv = xtrace._clip([[start, start + dur]], lo, hi)
            if not iv or name.split(".")[0] in xtrace.CONTAINERS:
                continue
            hop, stage = scopes.get(name, (None, None))
            op_ns[name] = op_ns.get(name, 0) + xtrace._length(iv)
            if is_async(name):
                by["async"].setdefault(hop, []).extend(iv)
                continue
            by["busy_ms"][None].extend(iv)
            by["hop"].setdefault(hop, []).extend(iv)
            if stage is not None:
                by["stage"].setdefault(stage, []).extend(iv)
        for kind, groups in by.items():
            for key, iv in groups.items():
                ns = xtrace._length(xtrace._union(iv))
                if kind == "busy_ms":
                    sums[kind] += ns
                else:
                    sums[kind][key] = sums[kind].get(key, 0) + ns
    per_round = ctx["chips"] * ctx["rounds"] * 1e6
    out = {"busy_ms": sums["busy_ms"] / per_round}
    for kind in ("hop", "stage", "async"):
        out[kind] = {k: v / per_round for k, v in sums[kind].items()}
    print(f"[bench] device ms per round by hop {out['hop']}, by stage "
          f"{out['stage']}; asynchronous ops by hop (left out) "
          f"{out['async']}; synchronous busy {out['busy_ms']}",
          file=sys.stderr, flush=True)
    for kind in (False, True):
        top = sorted((n for n in op_ns if is_async(n) == kind),
                     key=lambda n: -op_ns[n])[:TOP]
        print(f"[bench] {'asynchronous' if kind else 'synchronous'} ops "
              f"that took most, ms per round (hop, stage): " + ", ".join(
                  f"{n} {op_ns[n] / per_round:.4f} {scopes.get(n)}"
                  for n in top), file=sys.stderr, flush=True)
    _LAST[:] = [(trace, ctx, out)]
    return out
