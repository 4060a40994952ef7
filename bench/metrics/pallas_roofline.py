"""The Pallas kernels' least time over their device time in the trace.
Each Mosaic custom call's least time is the larger of its operations over
the bf16 peak and its bytes over HBM bandwidth, from the kernel's cost
function (``bench/kernels/<kernel>.py``, or ``streaming.py`` for a kernel
that reads its operands and writes its results once), with the shapes read
from the call's HLO text in the trace.  Nothing to read without a kernel in
the trace."""
from benchlib import metrics_io
from benchlib import spec as specs


def read(trace, ctx):
    kernels = ctx["pallas"]
    peaks = ctx["peaks"]
    least = spent = 0.0
    for ops in trace["ops"].values():
        for name, start, dur in ops:
            if name not in kernels or name not in trace["texts"]:
                continue
            bytes_in, bytes_out = metrics_io.call_bytes(trace["texts"][name])
            cost = (specs.module("kernels", kernels[name])
                    or specs.required_module("kernels", "streaming"))
            flops, nbytes = cost.cost(bytes_in, bytes_out)
            least += max(flops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
            spent += dur / 1e9
    if spent == 0.0:
        return None
    return 100.0 * least / spent
