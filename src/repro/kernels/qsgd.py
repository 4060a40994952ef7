"""Pallas TPU kernel: QSGD stochastic uniform quantization.

One HBM pass fuses (per-block max-abs scale -> normalise -> stochastic round
-> int8 cast). The pure-JAX version needs two passes (reduce, then map); at
the FL hot spot (quantise every parameter leaf every round, ~10^8–10^11 bytes)
the op is HBM-bandwidth-bound, so the fusion halves its memory term.

Layout: x is pre-reshaped to (nb, block); each grid step owns ROWS rows of
the block matrix in VMEM. ``block`` must be a multiple of 128 (lane width);
ROWS=8 keeps the tile at 8×block×4 B (e.g. 64 KiB for block=2048) — well
inside VMEM. Stochastic-rounding uniforms are an *input* (generated with
jax.random outside) so the kernel is bit-reproducible against ``ref.py``.

The per-row scale leaves the kernel as an (nb, 1) column and is reshaped to
(nb,) outside: Mosaic refuses a rank-1 ``(ROWS,)`` block (a rank-1 block must
span the array or be a multiple of 128 lanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import ROWS


def _kernel(x_ref, u_ref, q_ref, scale_ref, *, levels: int):
    x = x_ref[...]                                   # (ROWS, block) f32
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    y = x / jnp.maximum(scale, 1e-30) * levels
    q = jnp.floor(y + u_ref[...])
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def qsgd_quantize_blocked(xb, u, bits=8, interpret=False):
    """xb, u: (nb, block) f32. Returns (q int8 (nb, block), scale f32 (nb,))."""
    nb, block = xb.shape
    assert nb % ROWS == 0, (nb, ROWS)
    levels = 2 ** (bits - 1) - 1
    grid = (nb // ROWS,)
    q, scale = pl.pallas_call(
        functools.partial(_kernel, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xb, u)
    return q, scale.reshape(nb)
