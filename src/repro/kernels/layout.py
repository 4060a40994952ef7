"""Block layout shared by the row-blocked Pallas wire kernels.

``ROWS`` rows of the (nb, block) matrix make one grid step (8 sublanes of
f32).  ``SCALAR`` is a (1, 1) SMEM block for a per-call scalar such as a
threshold: it stays a legal block when the engine vmaps the call over
clients, where a rank-1 (1,) block batched to (C, 1) is refused.
"""
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8
SCALAR = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
