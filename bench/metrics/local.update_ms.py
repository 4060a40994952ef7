"""Host-clock span of the dispatch body's local update on the clients one
device holds (``benchlib.layer_spans``)."""


def read(trace, ctx):
    return ctx["spans"].get("local.update_ms")
