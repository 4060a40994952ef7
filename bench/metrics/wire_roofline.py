"""The least HBM time of the bytes the wire layer has to move
(``metrics_io.wire_bytes``: updates in, residual in and out, decoded rows
and payload out) over ``wire.codec_ms``.  The count does not depend on how
the wire computes top-k or quantizes."""


def read(trace, ctx):
    ms = ctx["spans"].get("wire.codec_ms")
    if not ms:
        return None
    least_s = ctx["wire_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
