"""Device time per round of the synchronous operations under the round
program's ``hop.local_update`` scope (``benchlib.scoped``): the clients'
local training, measured inside the timed round program."""
from benchlib import scoped


def read(trace, ctx):
    t = scoped.times(trace, ctx)
    return None if t is None else t["hop"].get("local_update", 0.0)
