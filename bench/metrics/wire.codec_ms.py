"""Host-clock span of the dispatch body's wire rows (encode, error feedback,
masks, decode) on the clients one device holds (``benchlib.layer_spans``)."""


def read(trace, ctx):
    return ctx["spans"].get("wire.codec_ms")
