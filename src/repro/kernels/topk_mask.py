"""Pallas TPU kernels of the top-k stage.

``threshold_sparsify_blocked`` — fused threshold-sparsify + error-feedback
residual. Top-k sparsification with error feedback performs, per round and
per leaf:
    kept  = x * (|x| >= t)        (the update that goes on the wire)
    resid = x - kept              (the error-feedback memory)
Fusing both into one HBM pass halves the memory traffic of the EF hot loop
(vs materialising `kept` then recomputing `x - kept`).

``count_at_least_rows`` — the threshold search's read of x: how many |x|
bit patterns lie at or above each of up to 16 candidates, in one pass with
nothing n-long written (XLA keeps an n-long copy of the bit pattern for
the same count). Index *extraction* (compaction to k slots) stays in XLA,
in ``compress.sparsification._topk_select``: a search for the k-th
magnitude, per-row kept counts and a sort of the k survivors — TPUs have
no efficient in-kernel compaction; see DESIGN.md §6.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import ROWS, SCALAR

LANES = 128
COUNT_ROWS = 2048       # rows of LANES per grid step of the count: 1 MiB
CANDIDATES = 16


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


def _count_kernel(x_ref, cand_ref, cnt_ref, *, n):
    rows = x_ref.shape[0]
    bits = jax.lax.bitcast_convert_type(jnp.abs(x_ref[...]), jnp.int32)
    row = pl.program_id(0) * rows + jax.lax.broadcasted_iota(
        jnp.int32, bits.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1)
    # pad lanes and the rows past the end of a partial block count nowhere
    bits = jnp.where(row * LANES + lane < n, bits, -1)
    # unrolled: an in-kernel loop over the candidates ran at half the speed
    for i in range(CANDIDATES):
        cnt_ref[i:i + 1, :] = jnp.sum((bits >= cand_ref[0, i]).astype(
            jnp.int32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def count_at_least_rows(xr, cand, n, interpret=False):
    """xr (R, LANES) f32 holding n entries row-major, cand (1, CANDIDATES)
    int32 -> (CANDIDATES,) int32: count of entries whose |x| bit pattern is
    >= each candidate."""
    r = xr.shape[0]
    rows = r if r <= COUNT_ROWS else COUNT_ROWS
    grid = -(-r // rows)
    cnt = pl.pallas_call(
        functools.partial(_count_kernel, n=n),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, CANDIDATES), lambda i: (0, 0),
                         memory_space=SCALAR.memory_space),
        ],
        out_specs=pl.BlockSpec((CANDIDATES, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * CANDIDATES, LANES),
                                       jnp.int32),
        interpret=interpret,
    )(xr, cand)
    return cnt.reshape(grid, CANDIDATES, LANES).sum(axis=(0, 2))
