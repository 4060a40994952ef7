"""Device time per round of the synchronous operations whose innermost
stage scope is ``stage.secagg`` (encode and decode; ``benchlib.scoped``).
Nothing to read in a cell whose wire has no such stage."""
from benchlib import scoped


def read(trace, ctx):
    t = scoped.times(trace, ctx)
    return None if t is None else t["stage"].get("secagg")
