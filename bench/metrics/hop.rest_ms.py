"""Device time per round of the synchronous operations under every other
hop scope of the round program (``data``, ``rng``, ``downlink``,
``select``, ``server_opt``, ``ledger``, ``finalize``, ...;
``benchlib.scoped``)."""
from benchlib import scoped


def read(trace, ctx):
    t = scoped.times(trace, ctx)
    if t is None:
        return None
    return sum(ms for hop, ms in t["hop"].items()
               if hop not in (None, "local_update", "wire"))
