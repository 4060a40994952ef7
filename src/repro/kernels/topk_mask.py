"""Pallas TPU kernel: fused threshold-sparsify + error-feedback residual.

Top-k sparsification with error feedback performs, per round and per leaf:
    kept  = x * (|x| >= t)        (the update that goes on the wire)
    resid = x - kept              (the error-feedback memory)
Fusing both into one HBM pass halves the memory traffic of the EF hot loop
(vs materialising `kept` then recomputing `x - kept`). Index *extraction*
(compaction to k slots) is data-dependent scatter/gather and stays in XLA
(`lax.top_k`) — TPUs have no efficient in-kernel compaction; see DESIGN.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import ROWS, SCALAR


def _kernel(x_ref, t_ref, kept_ref, resid_ref):
    x = x_ref[...]
    keep = jnp.abs(x) >= t_ref[0, 0]
    kept = jnp.where(keep, x, 0.0)
    kept_ref[...] = kept
    resid_ref[...] = x - kept


@functools.partial(jax.jit, static_argnames=("interpret",))
def threshold_sparsify_blocked(xb, thresh, interpret=False):
    """xb (nb, block) f32 -> (kept, resid) same shape."""
    nb, block = xb.shape
    assert nb % ROWS == 0
    t = jnp.reshape(thresh.astype(jnp.float32), (1, 1))
    return pl.pallas_call(
        _kernel,
        grid=(nb // ROWS,),
        in_specs=[
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
            SCALAR,
        ],
        out_specs=[
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), jnp.float32),
            jax.ShapeDtypeStruct((nb, block), jnp.float32),
        ],
        interpret=interpret,
    )(xb, t)
