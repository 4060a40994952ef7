"""Cost of a kernel that streams its operands in and its results out once
and does a few operations per element (the wire's quantize, ternarise,
mask and pack passes): no FLOPs worth counting, bytes in plus bytes out."""


def cost(bytes_in, bytes_out):
    return 0.0, bytes_in + bytes_out
