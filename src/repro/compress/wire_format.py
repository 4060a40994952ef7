"""Packed wire formats — int codes on the wire (DESIGN.md §10).

The staged pipeline ships each stage's payload in its *storage* dtype: int8
signs (8 bits for a ternary symbol), int8 QSGD levels (8 bits for a 4-bit
code). The ledger already reported the packed cost via ``entropy_bits``, but
the collective moved the wide buffers — the compression win lived in
accounting, not on the link. This module makes the packed form the payload
itself, so the ``all_gather`` operand IS the wire format and the HLO
collective bytes equal the ledger's ``wire_bits / 8`` exactly.

Byte layouts (little-endian within the byte; DESIGN.md §10):

  * ``pack2``  — 2-bit two's-complement codes, 4 per byte:
                 ``byte = c0 | c1<<2 | c2<<4 | c3<<6``; code -1 -> 0b11,
                 0 -> 0b00, +1 -> 0b01.  Length ``ceil(n/4)``; the tail
                 byte's unused fields are zero.
  * ``pack4``  — 4-bit two's-complement codes (range [-8, 7]), 2 per byte:
                 ``byte = c0 | c1<<4``.  Length ``ceil(n/2)``.  QSGD at
                 ``bits <= 4`` has levels in [-7, 7], so nibble packing is
                 lossless; ``bits > 4`` cannot pack and fails loudly.

Both pack the FLAT code vector.  Because every blocked kernel layout uses a
block length divisible by 4, byte ``i`` of the flat packing covers codes
``4i..4i+3`` in blocked layouts too — the Pallas fused pack kernels
(``repro.kernels.bitpack``) emit bit-identical bytes row by row, and the
flattened, sliced kernel output equals the pure-JAX flat packing exactly
(tests/test_kernel_parity.py round-trip cases).

``payload_nbytes`` sizes a pipeline's payload by ``jax.eval_shape`` — the
ground truth the ledger is tested against for every packable spec.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# wire formats a pipeline stage may ship: "staged" keeps the historical
# storage-dtype payloads (bit-exact with every pre-packing engine); "packed"
# ships the bit-packed codes (the "@fused" spec suffix / FLConfig.wire_format)
WIRE_FORMATS = ("staged", "packed")


def check_wire_format(wire: str) -> str:
    if wire not in WIRE_FORMATS:
        raise ValueError(
            f"unknown wire format {wire!r}; have {WIRE_FORMATS}")
    return wire


def packed_len(n: int, bits: int) -> int:
    """Bytes needed for n codes at ``bits`` bits per code (2 or 4)."""
    per = 8 // bits
    return -(-n // per)


def pack2(codes: jax.Array) -> jax.Array:
    """int8 ternary codes (n,) in {-1, 0, +1} -> uint8 (ceil(n/4),)."""
    n = codes.shape[0]
    pad = (-n) % 4
    u = (jnp.pad(codes, (0, pad)).astype(jnp.uint8) & 3).reshape(-1, 4)
    return (u[:, 0] | (u[:, 1] << 2) | (u[:, 2] << 4)
            | (u[:, 3] << 6)).astype(jnp.uint8)


def _unpack(packed: jax.Array, n: int, bits: int) -> jax.Array:
    """Code i is field ``i % per`` of byte ``i // per``, sign-extended from
    ``bits`` bits.  Written as a gather over the code index: the equivalent
    ``packed[:, None] >> shifts`` broadcast has a minor dimension of 2 or 4,
    which the TPU compiler tiles to 128 lanes, and under the engine's client
    vmap a (2, 1077412) nibble unpack took minutes to compile."""
    per = 8 // bits
    mask, off = (1 << bits) - 1, 1 << (bits - 1)
    i = jnp.arange(n, dtype=jnp.int32)
    u = (packed[i // per].astype(jnp.int32) >> (bits * (i % per))) & mask
    return (((u + off) & mask) - off).astype(jnp.int8)


def unpack2(packed: jax.Array, n: int) -> jax.Array:
    """uint8 (ceil(n/4),) -> int8 codes (n,) (2-bit sign extension)."""
    return _unpack(packed, n, 2)


def pack4(codes: jax.Array) -> jax.Array:
    """int8 codes (n,) in [-8, 7] -> uint8 (ceil(n/2),), low nibble first."""
    n = codes.shape[0]
    pad = (-n) % 2
    u = (jnp.pad(codes, (0, pad)).astype(jnp.uint8) & 15).reshape(-1, 2)
    return (u[:, 0] | (u[:, 1] << 4)).astype(jnp.uint8)


def unpack4(packed: jax.Array, n: int) -> jax.Array:
    """uint8 (ceil(n/2),) -> int8 codes (n,) (4-bit sign extension)."""
    return _unpack(packed, n, 4)


def payload_nbytes(pipe, n: int) -> int:
    """Exact bytes of ``pipe``'s encoded payload for a length-n leaf, via
    ``jax.eval_shape`` (no FLOPs).  This is what the aggregation collective
    gathers per client — for packable specs the ledger's ``wire_bits(n)``
    must equal ``8 * payload_nbytes`` (tests/test_kernel_parity.py)."""
    state = jax.eval_shape(lambda: pipe.init((n,)))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    x = jax.ShapeDtypeStruct((n,), jnp.float32)
    payload, _ = jax.eval_shape(pipe.encode, state, rng, x)
    return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(payload))
