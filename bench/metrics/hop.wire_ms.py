"""Device time per round of the synchronous operations under the round
program's ``hop.wire`` scope (``benchlib.scoped``): the uplink's stages,
error feedback, masks, decode and the weighted mean, measured inside the
timed round program."""
from benchlib import scoped


def read(trace, ctx):
    t = scoped.times(trace, ctx)
    return None if t is None else t["hop"].get("wire", 0.0)
