"""Public jit'd wrappers over the Pallas compression kernels.

On the CPU platform (the test path) the kernels execute with
``interpret=True`` — the kernel body runs through JAX's interpreter, proving
the Pallas logic without TPU hardware. On TPU the same calls always lower to
Mosaic, and any other platform is an error: there is no silent fallback to
the interpreter (``tests/test_tpu_compile.py`` compiles every kernel for a
described v5e).

Each wrapper handles the flat-vector <-> blocked layout plumbing so callers
(the compressors in ``repro.compress``) see the same flat-f32 interface as
the pure-JAX paths.  Layout contract (DESIGN.md §6): the kernel grid pads
the row count up to a multiple of ``ROWS``, but every wrapper slices its
outputs back to the *logical* payload — ``ceil(n / block)`` rows — before
returning, so pad lanes never reach the wire or the ledger.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import bitpack as _bp
from repro.kernels import count_sketch as _cs
from repro.kernels import qsgd as _qsgd
from repro.kernels import ternary as _tern
from repro.kernels import topk_mask as _topk

ROWS = _qsgd.ROWS


def _interpret() -> bool:
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run on 'tpu' (Mosaic) or interpreted on 'cpu'; "
            f"the default backend is {platform!r}")
    return platform == "cpu"


def _to_blocked(x, block):
    n = x.shape[0]
    nb = -(-n // block)
    nb = -(-nb // ROWS) * ROWS          # grid rows multiple of ROWS
    pad = nb * block - n
    return jnp.pad(x.astype(jnp.float32), (0, pad)).reshape(nb, block), pad


def _logical_rows(n, block):
    """Rows of the wire payload: pad rows beyond these carry no bytes."""
    return -(-n // block)


def qsgd_quantize(x, u, bits=8, block=2048):
    """Flat f32 (n,) + uniforms (n,) -> (q int8 (nb,block), scale f32 (nb,))
    with nb = ceil(n/block) — grid pad rows are sliced off."""
    n = x.shape[0]
    xb, pad = _to_blocked(x, block)
    ub, _ = _to_blocked(u, block)
    q, scale = _qsgd.qsgd_quantize_blocked(xb, ub, bits=bits,
                                           interpret=_interpret())
    nb = _logical_rows(n, block)
    return q[:nb], scale[:nb]


def qsgd_quantize_packed(x, u, bits=4, block=2048):
    """Fused quantize + nibble pack (``bits <= 4``): flat f32 (n,) +
    uniforms (n,) -> (packed uint8 (ceil(n/2),), scale f32 (nb,)) with
    nb = ceil(n/block).  The packed bytes equal ``wire_format.pack4`` of
    the staged kernel's flat codes bit-exactly; an odd short-carrier block
    (a chain carrier of odd k < block) cannot nibble-pack in-kernel, so it
    quantizes fused and packs in XLA (which fuses the shift/or anyway)."""
    n = x.shape[0]
    xb, pad = _to_blocked(x, block)
    ub, _ = _to_blocked(u, block)
    nb = _logical_rows(n, block)
    nbytes = -(-n // 2)
    if xb.shape[1] % 2:
        from repro.compress.wire_format import pack4
        q, scale = _qsgd.qsgd_quantize_blocked(xb, ub, bits=bits,
                                               interpret=_interpret())
        return pack4(q[:nb].reshape(-1)[:n]), scale[:nb]
    packed, scale = _bp.qsgd_pack_blocked(xb, ub, bits=bits,
                                          interpret=_interpret())
    return packed.reshape(-1)[:nbytes], scale[:nb]


def _k_from_fraction(n, fraction):
    """Static-shape-safe top-k count: ``fraction`` may be a traced scalar
    (e.g. the DGC warm-up's annealed fraction) — the same construction as
    ``MomentumCorrection._anneal_mask``."""
    frac = jnp.asarray(fraction, jnp.float32)
    return jnp.clip(jnp.round(n * frac).astype(jnp.int32), 1, n)


def _stc_threshold(x, fraction, max_fraction=None):
    """Top-k magnitude threshold for a static OR traced ``fraction``.

    Traced fractions (the DGC warm-up's per-round anneal) used to pay a
    full ``jnp.sort`` here; instead, one ``lax.top_k`` at the schedule's
    *static* widest k (``max_fraction``, e.g. ``final**(1/(W+1))`` — the
    round-0 fraction bounds every later round's) yields a descending prefix
    the traced order statistic is gathered from.  ``max_fraction=None``
    falls back to a full-length top_k (bit-identical to the sort).

    Perf trap: the order statistic must be read with a *reduction*
    (``jnp.min`` over the prefix), never a scalar slice or dynamic gather
    — a slice/gather fused into top_k's output defeats XLA's TopkRewriter
    pattern (sort+slice -> fast partial-select custom call) and silently
    reverts to a full variadic sort, ~4.5x slower on CPU at k = 0.1 n.
    The min over the descending prefix is the prefix's last element
    bit-exactly, and it vmaps (the engine's per-client wire vmap)."""
    n = x.shape[0]
    if isinstance(fraction, (int, float)):
        k = max(1, min(int(round(n * fraction)), n))
        return jnp.min(jax.lax.top_k(jnp.abs(x), k)[0])
    k = _k_from_fraction(n, fraction)
    kmax = (n if max_fraction is None
            else max(1, min(int(round(n * max_fraction)), n)))
    prefix = jax.lax.top_k(jnp.abs(x), kmax)[0]
    return jnp.min(jnp.where(jnp.arange(kmax) < jnp.minimum(k, kmax),
                             prefix, jnp.inf))


def stc_ternarize(x, fraction=0.01, block=2048, max_fraction=None):
    """Full STC compress: top-k threshold + fused ternarise pass.
    Returns (code int8 flat (n,), mu f32 scalar).  ``fraction`` may be a
    traced value (composes with ``dgc_warmup_rounds`` annealing); pass the
    schedule's static ``max_fraction`` so the threshold costs one
    ``lax.top_k`` over the widest-round prefix instead of a full sort."""
    n = x.shape[0]
    thresh = _stc_threshold(x, fraction, max_fraction)
    xb, pad = _to_blocked(x, block)
    code, psum, pcnt = _tern.ternarize_blocked(xb, thresh,
                                               interpret=_interpret())
    mu = psum.sum() / jnp.maximum(pcnt.sum(), 1.0)
    return code.reshape(-1)[:n], mu


def stc_ternarize_packed(x, fraction=0.01, block=2048, max_fraction=None):
    """Fused dense-STC wire format: top-k threshold + ONE ternarise+2-bit-pack
    pass (``repro.kernels.bitpack``).  Returns (packed uint8 flat
    (ceil(n/4),), mu f32 scalar) — the packed codes are exactly
    ``wire_format.pack2`` of ``stc_ternarize``'s codes, but the int8 code
    tensor never round-trips HBM."""
    n = x.shape[0]
    thresh = _stc_threshold(x, fraction, max_fraction)
    xb, pad = _to_blocked(x, block)
    packed, psum, pcnt = _bp.ternarize_pack_blocked(xb, thresh,
                                                    interpret=_interpret())
    mu = psum.sum() / jnp.maximum(pcnt.sum(), 1.0)
    return packed.reshape(-1)[:-(-n // 4)], mu


def ternarize_signs(x, block=2048):
    """The chainable Ternary stage's fused pass: full-support ternarise
    (threshold 0 keeps everything; flat pads are sign(0) = 0) returning
    (sign int8 flat (n,), sum|x| f32 scalar).  The caller finalises
    mu = sum|x| / n over the *logical* length, so pad lanes never enter
    the mean."""
    n = x.shape[0]
    xb, pad = _to_blocked(x, block)
    code, psum, _ = _tern.ternarize_blocked(xb, jnp.float32(0.0),
                                            interpret=_interpret())
    return code.reshape(-1)[:n], psum.sum()


def ternarize_signs_packed(x, block=2048):
    """Ternary's packed wire format in one fused pass: full-support
    ternarise + 2-bit pack.  Returns (packed uint8 flat (ceil(n/4),),
    sum|x| f32 scalar).  Pad lanes are sign(0) = 0 -> zero bits, so the
    flat byte slice is bit-identical to ``wire_format.pack2`` of the
    unpacked signs."""
    n = x.shape[0]
    xb, pad = _to_blocked(x, block)
    packed, psum, _ = _bp.ternarize_pack_blocked(xb, jnp.float32(0.0),
                                                 interpret=_interpret())
    return packed.reshape(-1)[:-(-n // 4)], psum.sum()


def threshold_sparsify(x, thresh, block=2048):
    """Fused (kept, error-feedback residual) in one pass. Flat f32 in/out."""
    n = x.shape[0]
    xb, pad = _to_blocked(x, block)
    kept, resid = _topk.threshold_sparsify_blocked(xb, thresh,
                                                   interpret=_interpret())
    return kept.reshape(-1)[:n], resid.reshape(-1)[:n]


def sketch(x, rows=5, cols=4096, seed=17):
    """Count-sketch via the one-hot-MXU kernel. Flat f32 (n,) -> (rows, cols)."""
    from repro.compress.sketch import hash_params
    a, b = hash_params(rows, seed)
    return _cs.count_sketch(x, a, b, rows, cols, interpret=_interpret())
