"""Share of the window's synchronous device time spent in operations that
no hop scope of the round program owns (``benchlib.scoped``): the blind
spot of the per-hop metrics."""
from benchlib import scoped


def read(trace, ctx):
    t = scoped.times(trace, ctx)
    if t is None or not t["busy_ms"]:
        return None
    return 100.0 * t["hop"].get(None, 0.0) / t["busy_ms"]
