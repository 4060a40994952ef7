"""Pallas TPU kernel: count-sketch accumulation (FetchSGD [66]).

GPU FetchSGD scatters x_i into S[j, h_j(i)] with atomics. TPUs have no fast
scatter unit — the TPU-native adaptation recasts the hash-scatter as a
**one-hot matmul on the MXU**:

    S[j, cols_t] += (s_j ⊙ x_chunk) · onehot(h_j(chunk))ᵀ      (1, C)·(C, T)

The hash h_j(i) = ((a_j·i + b_j) mod 2^32) mod cols and sign s_j(i) are
computed in-kernel from ``broadcasted_iota`` over the *global* element index,
so only x itself is read from HBM; the hash parameters sit in SMEM.

Layout: x is padded and viewed as (chunks, CHUNK); each grid step reads ROWS
chunks as one (ROWS, CHUNK) tile and walks them in a loop.  The one-hot is
built transposed, (T, CHUNK) with buckets on sublanes, one tile of T = TILE
buckets at a time, so the intermediate stays at TILE·CHUNK·4 B = 1 MiB of
VMEM whatever ``cols`` is.  The (rows, cols) output block is revisited by
every step — initialised at step 0, accumulated thereafter (standard Pallas
revisiting-output reduction); TPU grids run sequentially per core, so the
accumulation is race-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 1024
ROWS = 8                    # chunks per grid step
TILE = 256                  # buckets per one-hot tile


def _kernel(a_ref, b_ref, x_ref, out_ref, *, rows: int, cols: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lane = jax.lax.broadcasted_iota(jnp.uint32, (1, CHUNK), 1)

    def chunk(r, carry):
        base = ((step * ROWS + r) * CHUNK).astype(jnp.uint32)
        x = x_ref[pl.ds(r, 1), :]                    # (1, CHUNK)
        idx = base + lane
        for j in range(rows):
            ab = a_ref[0, j] * idx + b_ref[0, j]     # uint32 wraparound hash
            h = (ab % jnp.uint32(cols)).astype(jnp.int32)
            s = jnp.where((ab // jnp.uint32(cols)) % 2 == 0, 1.0, -1.0)
            sx = s.astype(jnp.float32) * x
            for c0 in range(0, cols, TILE):
                t = min(TILE, cols - c0)
                onehot = (jax.lax.broadcasted_iota(jnp.int32, (t, CHUNK), 0)
                          + c0 == h).astype(jnp.float32)
                part = jax.lax.dot_general(
                    sx, onehot, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)   # (1, t)
                out_ref[j:j + 1, c0:c0 + t] += part
        return carry

    jax.lax.fori_loop(0, ROWS, chunk, 0)


@functools.partial(jax.jit, static_argnames=("rows", "cols", "interpret"))
def count_sketch(x, a, b, rows: int, cols: int, interpret=False):
    """x (n,) f32, any n; a, b (rows,) uint32 hash params.
    Returns S (rows, cols) f32."""
    n = x.shape[0]
    # zero-pad to whole grid steps (the one copy): pads add 0 to any bucket
    steps = -(-n // (ROWS * CHUNK))
    xb = jnp.pad(x.astype(jnp.float32),
                 (0, steps * ROWS * CHUNK - n)).reshape(steps * ROWS, CHUNK)
    # (1, rows) blocks stay legal when the engine vmaps the call
    smem = pl.BlockSpec((1, rows), lambda i: (0, 0), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, cols=cols),
        grid=(steps,),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((ROWS, CHUNK), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, cols), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        interpret=interpret,
    )(a.astype(jnp.uint32).reshape(1, rows),
      b.astype(jnp.uint32).reshape(1, rows), xb)
