"""Pallas TPU kernels: bit-packed wire formats, fused into the encode pass.

The staged kernels (``ternary.py``, ``qsgd.py``) emit int8 codes that a
separate pack pass would have to re-read from HBM.  These kernels fuse the
bitpack into the quantize/ternarize tile loop, so per grid step the f32 tile
is read once and only the *packed* bytes are written — the uncompressed
tensor and the unpacked codes never round-trip HBM (DESIGN.md §10):

  * ``ternarize_pack_blocked`` — threshold -> sign -> 2-bit pack + the mu
    partial sums, one pass (the fused dense-STC wire format).
  * ``qsgd_pack_blocked``      — scale -> normalise -> stochastic round ->
    nibble pack + per-row scale, one pass (``bits <= 4`` only).
  * ``pack_codes_blocked`` / ``unpack_codes_blocked`` — standalone pack and
    unpack passes over an int8 code matrix (2 or 4 bits/code), used by the
    round-trip parity tests and as the building block for future
    compress-into-collective fusions.

Byte layout matches ``repro.compress.wire_format`` exactly: little-endian
fields within each byte, byte ``j`` of a row covering codes ``4j..4j+3``
(2-bit) or ``2j..2j+1`` (4-bit).  ``block`` must be divisible by the codes
per byte, so the flattened packed rows equal the flat-vector packing of the
flattened codes — the cross-backend payload-identity the parity harness
asserts.

Mosaic has no strided lane slice (``u[:, 0::4]``) and no int8 vector
arithmetic, so packing runs on the MXU: byte ``j`` is the weighted sum
``Σ_k field[per·j + k] · 2^(bits·k)`` of its ``per = 8 // bits`` adjacent
fields, i.e. a matmul of the (ROWS, width) field tile with a constant 0/2^i
matrix; unpacking is the transpose (each byte repeated ``per`` times)
followed by an int32 shift and mask.  Fields (< 16), weights (powers of two)
and bytes (<= 255) are all exact in bf16, and the products accumulate in f32,
so both are bit-exact.  The matrices are kernel inputs with a constant block
index, fetched into VMEM once per call; each MXU pass covers 128 packed
lanes.  Per-row partials leave as (nb, 1) columns (Mosaic refuses a rank-1
``(ROWS,)`` block) and are reshaped to (nb,) outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import ROWS, SCALAR

PACKED_LANES = 128          # packed bytes produced per MXU pass


def _pack_matrix(width, bits):
    """(width, width // per) bf16 with W[i, j] = 2^(bits·(i % per)) where
    i // per == j: a (ROWS, width) field tile times W is the packed tile."""
    per = 8 // bits
    i = jnp.arange(width)[:, None]
    j = jnp.arange(width // per)[None, :]
    w = jnp.where(i // per == j, 2.0 ** (bits * (i % per)), 0.0)
    return w.astype(jnp.bfloat16)


def _spread_matrix(pwidth, bits):
    """(pwidth, pwidth·per) bf16 0/1 matrix: a packed tile times it repeats
    every byte over the ``per`` code lanes it covers."""
    per = 8 // bits
    j = jnp.arange(pwidth)[:, None]
    i = jnp.arange(pwidth * per)[None, :]
    return (i // per == j).astype(jnp.bfloat16)


def _full_spec(a):
    return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)


def _pack_into(out_ref, fields, w_ref, bits):
    """fields f32 (ROWS, width), values in [0, 2^bits) -> uint8 bytes
    stored into out_ref (ROWS, width // per), one MXU pass per chunk."""
    per = 8 // bits
    cw = w_ref.shape[0]
    width = fields.shape[1]
    f = fields.astype(jnp.bfloat16)
    for c0 in range(0, width, cw):
        tw = min(cw, width - c0)
        w = w_ref[...] if tw == cw else w_ref[:tw, :tw // per]
        byte = jnp.dot(f[:, c0:c0 + tw], w,
                       preferred_element_type=jnp.float32)
        out_ref[:, c0 // per:(c0 + tw) // per] = (
            byte.astype(jnp.int32).astype(jnp.uint8))


def _tern_pack_kernel(x_ref, t_ref, w_ref, packed_ref, psum_ref, pcnt_ref):
    x = x_ref[...]                                   # (ROWS, block) f32
    t = t_ref[0, 0]
    mag = jnp.abs(x)
    keep = mag >= t
    # 2-bit two's-complement field of sign(x)·keep: +1 -> 1, -1 -> 3, 0 -> 0
    field = jnp.where(keep & (x > 0), 1.0, jnp.where(keep & (x < 0), 3.0, 0.0))
    _pack_into(packed_ref, field, w_ref, 2)
    psum_ref[...] = jnp.sum(jnp.where(keep, mag, 0.0), axis=1, keepdims=True)
    pcnt_ref[...] = jnp.sum(keep.astype(jnp.float32), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ternarize_pack_blocked(xb, thresh, interpret=False):
    """xb (nb, block) f32, thresh () f32 -> (packed uint8 (nb, block//4),
    psum f32 (nb,), pcnt f32 (nb,)).  Pad lanes (x == 0) pack to zero bytes
    for any threshold, so slicing the flat bytes to ceil(n/4) is exact."""
    nb, block = xb.shape
    assert nb % ROWS == 0 and block % 4 == 0, (nb, block)
    t = jnp.reshape(thresh.astype(jnp.float32), (1, 1))
    w = _pack_matrix(min(block, 4 * PACKED_LANES), 2)
    packed, psum, pcnt = pl.pallas_call(
        _tern_pack_kernel,
        grid=(nb // ROWS,),
        in_specs=[
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
            SCALAR,
            _full_spec(w),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, block // 4), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block // 4), jnp.uint8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xb, t, w)
    return packed, psum.reshape(nb), pcnt.reshape(nb)


def _qsgd_pack_kernel(x_ref, u_ref, w_ref, packed_ref, scale_ref, *, levels):
    x = x_ref[...]                                   # (ROWS, block) f32
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    y = x / jnp.maximum(scale, 1e-30) * levels
    q = jnp.floor(y + u_ref[...])                    # integral, in [-8, 7]
    _pack_into(packed_ref, jnp.where(q < 0, q + 16.0, q), w_ref, 4)
    scale_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def qsgd_pack_blocked(xb, u, bits=4, interpret=False):
    """xb, u: (nb, block) f32 -> (packed uint8 (nb, block//2), scale f32
    (nb,)).  ``bits <= 4`` so levels fit the [-8, 7] nibble losslessly."""
    nb, block = xb.shape
    assert nb % ROWS == 0 and block % 2 == 0, (nb, block)
    assert 2 <= bits <= 4, bits
    levels = 2 ** (bits - 1) - 1
    w = _pack_matrix(min(block, 2 * PACKED_LANES), 4)
    packed, scale = pl.pallas_call(
        functools.partial(_qsgd_pack_kernel, levels=levels),
        grid=(nb // ROWS,),
        in_specs=[
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
            _full_spec(w),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, block // 2), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block // 2), jnp.uint8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xb, u, w)
    return packed, scale.reshape(nb)


def _pack_only_kernel(c_ref, w_ref, p_ref, *, bits):
    field = c_ref[...].astype(jnp.int32) & ((1 << bits) - 1)
    _pack_into(p_ref, field.astype(jnp.float32), w_ref, bits)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def pack_codes_blocked(cb, bits=2, interpret=False):
    """int8 codes (nb, block) -> packed uint8 (nb, block*bits//8)."""
    nb, block = cb.shape
    per = 8 // bits
    assert nb % ROWS == 0 and block % per == 0 and bits in (2, 4)
    w = _pack_matrix(min(block, per * PACKED_LANES), bits)
    return pl.pallas_call(
        functools.partial(_pack_only_kernel, bits=bits),
        grid=(nb // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
                  _full_spec(w)],
        out_specs=[pl.BlockSpec((ROWS, block // per), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, block // per), jnp.uint8)],
        interpret=interpret,
    )(cb, w)[0]


def _unpack_only_kernel(p_ref, s_ref, c_ref, *, bits):
    per = 8 // bits
    cw = s_ref.shape[0]
    pwidth = p_ref.shape[1]
    mask, off = (1 << bits) - 1, 1 << (bits - 1)
    p = p_ref[...].astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
    for c0 in range(0, pwidth, cw):
        tw = min(cw, pwidth - c0)
        s = s_ref[...] if tw == cw else s_ref[:tw, :tw * per]
        rep = jnp.dot(p[:, c0:c0 + tw], s,
                      preferred_element_type=jnp.float32).astype(jnp.int32)
        lane = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 1)
        u = (rep >> ((lane % per) * bits)) & mask
        code = ((u + off) & mask) - off              # sign-extend the field
        c_ref[:, c0 * per:(c0 + tw) * per] = code.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def unpack_codes_blocked(pb, bits=2, interpret=False):
    """packed uint8 (nb, pblock) -> int8 codes (nb, pblock*8//bits)."""
    nb, pblock = pb.shape
    per = 8 // bits
    assert nb % ROWS == 0 and bits in (2, 4)
    s = _spread_matrix(min(pblock, PACKED_LANES), bits)
    return pl.pallas_call(
        functools.partial(_unpack_only_kernel, bits=bits),
        grid=(nb // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, pblock), lambda i: (i, 0)),
                  _full_spec(s)],
        out_specs=[pl.BlockSpec((ROWS, pblock * per), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, pblock * per), jnp.int8)],
        interpret=interpret,
    )(pb, s)[0]
