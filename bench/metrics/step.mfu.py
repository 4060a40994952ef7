"""The round's model FLOPs (the local update's forward and backward, with no
recompute, from ``bench/models/<family>.py``) over the traced run's round
time on the host clock, the chips and their bf16 peak."""


def read(trace, ctx):
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["round_flops"] / (ctx["round_s"] * peak)
