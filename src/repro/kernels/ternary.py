"""Pallas TPU kernel: STC ternarisation (threshold -> {−mu, 0, +mu} partials).

Given the global top-k magnitude threshold t (computed once outside with
``lax.top_k``), one fused HBM pass emits per-tile ternary codes plus the
partial sums needed for mu = mean(|x| over the support):

    code  = sign(x) * (|x| >= t)          int8
    psum  = Σ_tile |x| · (|x| >= t)       f32 per grid row
    pcnt  = Σ_tile (|x| >= t)             f32 per grid row

The caller finalises mu = Σpsum / Σpcnt (a tiny reduction) — so the whole STC
compress is 1 top-k + 1 fused pass instead of 3 elementwise passes.  The
per-row partials leave the kernel as (nb, 1) columns (Mosaic refuses a
rank-1 ``(ROWS,)`` block) and are reshaped to (nb,) outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import ROWS, SCALAR


def _kernel(x_ref, t_ref, code_ref, psum_ref, pcnt_ref):
    x = x_ref[...]                                   # (ROWS, block)
    t = t_ref[0, 0]
    mag = jnp.abs(x)
    keep = mag >= t
    code_ref[...] = (jnp.sign(x) * keep).astype(jnp.int8)
    psum_ref[...] = jnp.sum(jnp.where(keep, mag, 0.0), axis=1, keepdims=True)
    pcnt_ref[...] = jnp.sum(keep.astype(jnp.float32), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ternarize_blocked(xb, thresh, interpret=False):
    """xb (nb, block) f32, thresh () f32 ->
    (code int8 (nb, block), psum f32 (nb,), pcnt f32 (nb,))."""
    nb, block = xb.shape
    assert nb % ROWS == 0
    grid = (nb // ROWS,)
    t = jnp.reshape(thresh.astype(jnp.float32), (1, 1))
    code, psum, pcnt = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
            SCALAR,
        ],
        out_specs=[
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xb, t)
    return code, psum.reshape(nb), pcnt.reshape(nb)
