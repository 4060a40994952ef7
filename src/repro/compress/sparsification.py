"""Sparsification stages (survey §III.B.5 — Sparsification).

  * ``topk``     — magnitude top-k with (values, indices) wire format; the GGS
    [67] setting. Carrier = the k surviving values, so further stages refine
    them: ``chain(topk, ternary)`` *is* STC, ``chain(topk, qsgd)`` is the
    quantised-sparse combined scheme. Biased -> error feedback.
  * ``ternary``  — STC's quantization half [39]: values -> sign(x)·mean(|x|).
    Wire = signs + one scalar; the paper's Golomb coding is reported via
    ``entropy_bits``.
  * ``stc``      — legacy name for ``chain(topk, ternary)`` (bit-for-bit the
    old monolithic STC compressor).
  * ``sbc``      — Sparse Binary Compression [69]: keep only the dominant-sign
    half of the top-k support, average its magnitudes (1 fewer bit than STC).
  * ``randmask`` — CPFed [68]: data-independent random mask (unbiased after
    1/p rescale) + optional Gaussian noise on the surviving values (DP).
    Carrier = surviving values (only they travel; the mask rides a seed).

All operate on flattened f32 leaves; k is a static fraction of n (fixed shapes
under jit — matching the source papers' fixed-sparsity setting).

``topk`` selects without sorting the leaf (``_topk_select``): the k-th
magnitude by a search over the float's bit pattern, the survivors by a
descent through per-row kept counts, then one sort of the k survivors —
bit-identical to ``lax.top_k`` (DESIGN.md §6).  ``SBC.encode``,
``RandMask._idx``, ``FusedSTC`` (``ops._stc_threshold``),
``MomentumCorrection._anneal_mask``, ``sketch`` and ``core/selection.py``
keep ``lax.top_k``: no benchmarked wire runs them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.compress.api import CommTransform, register, register_stage


def _k(n, fraction):
    return max(1, int(round(n * fraction)))


SORT_BELOW = 1 << 13    # shorter leaves take lax.top_k on the TPU
LANES = 128             # row width of the kept-count tree
WAYS = 16               # the threshold search splits its interval 16 ways
CHUNK = 1 << 16         # survivors located per step of the descent


def _mag_bits(x):
    """|x| as int32 bits: for non-NaN floats they order as the magnitude."""
    return jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32)


def _kth_magnitude(xr, n, k):
    """(t, count(bits > t)): t is the bit pattern of the k-th largest |x|,
    x the n entries of the row view ``xr``.

    Keeps count(bits >= lo) >= k > count(bits >= hi) over [0, +inf + 1) and
    cuts the interval at ``WAYS - 1`` points per read of x (one Pallas
    pass, ``ops.count_at_least``), until hi = lo + 1: 8 reads."""
    from repro.kernels import ops
    cuts = jnp.arange(1, WAYS, dtype=jnp.int32)
    reads, d = 0, 0x7F800001
    while d > 1:
        reads, d = reads + 1, -(-d // WAYS)

    def step(_, c):
        lo, hi, n_hi = c
        d = hi - lo
        cand = lo + (d // WAYS) * cuts + (d % WAYS) * cuts // WAYS
        cnt = ops.count_at_least(xr, cand, n)
        ok = cnt >= k
        above = jnp.where(ok, jnp.int32(0x7FFFFFFF), cand)
        first = jnp.argmin(above)
        hit = above[first] < hi
        return (jnp.max(jnp.where(ok, cand, lo)),
                jnp.where(hit, cand[first], hi),
                jnp.where(hit, cnt[first], n_hi))

    t, _, n_gt = jax.lax.fori_loop(
        0, reads, step, (jnp.int32(0), jnp.int32(0x7F800001), jnp.int32(0)))
    return t, n_gt


def _rows(v):
    """(ceil(n / LANES), LANES) view of a 1-D vector, zero-padded."""
    m = -(-v.shape[0] // LANES) * LANES
    return jnp.pad(v, (0, m - v.shape[0])).reshape(-1, LANES)


def _row_cumsum(v, bound):
    """Inclusive prefix sum along the last axis (LANES) of int32 entries in
    [0, bound], exact: each 8-bit digit times the upper-triangular ones on
    the MXU (bf16 holds a digit exactly, f32 sums of 128 digits too)."""
    tri = jnp.triu(jnp.ones((LANES, LANES), jnp.bfloat16))
    out, shift = 0, 0
    while True:
        digit = v if bound < 256 else (v >> shift) & 255
        part = jnp.dot(digit.astype(jnp.bfloat16), tri,
                       preferred_element_type=jnp.float32)
        out = out + (part.astype(jnp.int32) << shift)
        shift += 8
        if bound >> shift == 0:
            return out


def _cumsum(v, bound):
    """Inclusive prefix sum of a 1-D int32 vector with entries in
    [0, bound], a row of LANES at a time."""
    rows = _row_cumsum(_rows(v), bound)
    if rows.shape[0] > 1:
        totals = rows[:, -1]
        rows = rows + (_cumsum(totals, bound * LANES) - totals)[:, None]
    return rows.reshape(-1)[:v.shape[0]]


def _pick(row, bound, before, j):
    """Per target j: the lane of a row of kept counts (each at most
    ``bound``) that holds the j-th kept entry, and the kept count before
    that lane."""
    incl = before[:, None] + _row_cumsum(row, bound)
    lane = jnp.sum(incl < j[:, None], axis=1, dtype=jnp.int32)
    left = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1) < lane[:, None]
    return lane, before + jnp.sum(jnp.where(left, row, 0), axis=1,
                                  dtype=jnp.int32)


@functools.partial(jax.jit, static_argnums=1)
def _topk_select(x, k):
    """``(x[idx], idx)`` for the k largest |x|, largest first, ties by
    ascending index: bit-identical to ``lax.top_k(jnp.abs(x), k)``'s
    indices, with no sort over the n entries (x has no NaN).

    1. t = the k-th largest |x| (``_kth_magnitude``). Kept: every entry
       above t and the first ``k - count(> t)`` entries equal to t, those
       at or before the position ``cut`` of the last one.
    2. One read of x counts the kept entries of each row of LANES; sums of
       LANES counts build the levels above until one row remains.
    3. The j-th kept entry is found by descending the levels, one gathered
       row per level, ``CHUNK`` targets at a time: the k indices come out
       in ascending order.
    4. One stable sort of the k survivors by descending magnitude.

    Nothing n-long is kept but the rows of x that XLA may lay out for the
    row reads; the per-row counts are n / LANES long."""
    n = x.shape[0]
    xr = _rows(x)
    t, n_gt = _kth_magnitude(xr, n, k)
    r = k - n_gt                                # ties at t to keep

    def bits_pos(rows, row_id):
        pos = row_id[:, None] * LANES + jax.lax.broadcasted_iota(
            jnp.int32, rows.shape, 1)
        return _mag_bits(rows), pos

    bits, pos = bits_pos(xr, jnp.arange(xr.shape[0], dtype=jnp.int32))
    ties = jnp.sum((bits == t) & (pos < n), axis=1, dtype=jnp.int32)
    ties_before = _cumsum(ties, LANES) - ties
    levels = [jnp.sum(bits > t, axis=1, dtype=jnp.int32)
              + jnp.clip(r - ties_before, 0, ties)]
    # the position of the r-th tie: the last one kept
    last = jnp.sum(ties_before + ties < r, dtype=jnp.int32)
    bits, pos = bits_pos(jax.lax.dynamic_slice_in_dim(xr, last, 1),
                         last[None])
    tie_rank = ties_before[last] + _row_cumsum(
        ((bits == t) & (pos < n)).astype(jnp.int32), 1)
    cut = last * LANES + jnp.sum(tie_rank < r, dtype=jnp.int32)
    while levels[-1].shape[0] > LANES:
        levels.append(jnp.sum(_rows(levels[-1]), axis=1, dtype=jnp.int32))
    top = levels[-1]
    top_incl = _cumsum(top, min(n, LANES ** len(levels)))

    kc = min(k, CHUNK)
    steps = -(-k // kc)

    def descend(c, out):
        j = jnp.minimum(c * kc + jnp.arange(1, kc + 1, dtype=jnp.int32), k)
        left = top_incl[None, :] < j[:, None]
        node = jnp.sum(left, axis=1, dtype=jnp.int32)
        before = jnp.sum(jnp.where(left, top[None, :], 0), axis=1,
                         dtype=jnp.int32)
        for depth in range(len(levels) - 1, 0, -1):
            lane, before = _pick(_rows(levels[depth - 1])[node],
                                 min(n, LANES ** depth), before, j)
            node = node * LANES + lane
        rows = xr[node]
        bits, pos = bits_pos(rows, node)
        kept = (bits > t) | ((bits == t) & (pos <= cut))
        lane, _ = _pick(kept.astype(jnp.int32), 1, before, j)
        # the one lane's bits, by a max: exact for -0.0, unlike a sum
        lanes = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
        val = jax.lax.bitcast_convert_type(jnp.max(jnp.where(
            lanes == lane[:, None],
            jax.lax.bitcast_convert_type(rows, jnp.int32),
            jnp.iinfo(jnp.int32).min), axis=1), x.dtype)
        return (jax.lax.dynamic_update_slice(out[0], node * LANES + lane,
                                             (c * kc,)),
                jax.lax.dynamic_update_slice(out[1], val, (c * kc,)))

    out = (jnp.zeros((steps * kc,), jnp.int32),
           jnp.zeros((steps * kc,), x.dtype))
    idx, vals = (descend(0, out) if steps == 1 else
                 jax.lax.fori_loop(0, steps, descend, out))
    _, idx, vals = jax.lax.sort((-_mag_bits(vals[:k]), idx[:k], vals[:k]),
                                num_keys=1, is_stable=True)
    return vals, idx


def _topk(x, k):
    """``(x[idx], idx)`` for the k largest |x|, as ``lax.top_k`` orders
    them.  On the TPU ``lax.top_k`` sorts the whole leaf, so a leaf of
    ``SORT_BELOW`` entries or more takes ``_topk_select``; a shorter leaf,
    and every leaf where the Pallas kernels are interpreted (the CPU, whose
    top-k is a partial sort), is faster through ``lax.top_k`` (PERF.md §6).
    Both give the same bits."""
    from repro.kernels import ops
    if (x.shape[0] >= SORT_BELOW and jax.default_backend() in ("cpu", "tpu")
            and not ops._interpret()):
        return _topk_select(x, k)
    idx = jax.lax.top_k(jnp.abs(x), k)[1]
    return x[idx], idx


class TopK(CommTransform):
    """Magnitude top-k; index extraction is ``_topk_select`` on both
    backends (threshold search, per-row kept counts, a sort of the k
    survivors), bit-identical to ``lax.top_k`` with its ties by ascending
    index.  ``backend="kernel"``: the dense masking pass runs through the
    fused ``threshold_sparsify`` Pallas kernel at the k-th magnitude and the
    payload values are gathered from its output, ``kept[idx] == x[idx]``
    bit for bit (DESIGN.md §6)."""
    biased = True
    carrier_key = "vals"
    kernel_capable = True

    def __init__(self, fraction=0.01, block=2048, backend="jax"):
        self.fraction = fraction
        self.block = block
        self.backend = backend
        self.name = f"topk{fraction:g}" + \
            ("@kernel" if backend == "kernel" else "")

    def encode(self, state, rng, x):
        n = x.shape[0]
        k = _k(n, self.fraction)
        vals, idx = _topk(x, k)
        if self.backend == "kernel":
            from repro.kernels import ops
            # the fused kernel pass at the k-th magnitude produces the
            # dense masked vector the payload values are gathered from
            # (kept[idx] == x[idx] bit-exactly, ties included). The pass
            # also emits the EF residual — wiring it into the
            # ErrorFeedback wrapper is the roadmap's TPU HBM win.
            kept, _ = ops.threshold_sparsify(x, jnp.abs(vals[-1]),
                                             self.block)
            return {"vals": kept[idx], "idx": idx}, state
        return {"vals": vals, "idx": idx}, state

    def decode(self, payload, n):
        out = jnp.zeros((n,), jnp.float32)
        return out.at[payload["idx"]].set(payload["vals"].astype(jnp.float32))

    def carrier_len(self, n):
        return _k(n, self.fraction)

    def meta_bits(self, n):
        return _k(n, self.fraction) * 32.0       # int32 indices

    def meta_entropy_bits(self, n):
        k = _k(n, self.fraction)
        idx_bits = math.log2(max(n / k, 2.0)) + 2      # Golomb-coded gaps
        return k * idx_bits

    def carrier_hint(self, n):
        # the carrier is the top-|x| tail: a following quantizer's levels
        # concentrate near full scale, where Elias-gamma is expensive
        return {"kind": "top_tail", "fraction": _k(n, self.fraction) / n}


class Ternary(CommTransform):
    """Ternarisation to ±mean(|x|) — STC's quantizer, as a chainable stage.

    ``backend="kernel"``: signs + the |x| partial sums come from one fused
    ``ternarize_blocked`` pass. Signs are bit-exact; mu differs from the
    pure path by reduction *order* only (per-row partials then a row sum vs
    one flat sum) — the documented bounded-ULP parity class.

    ``wire="packed"`` (the ``@fused`` suffix): the payload is the 2-bit
    packed sign vector — ``8*ceil(n/4) + 32`` wire bits instead of
    ``8n + 32``, ledger == payload bytes exactly (DESIGN.md §10).  The
    kernel path packs inside the ternarise pass (``kernels.bitpack``)."""
    biased = True
    kernel_capable = True

    def __init__(self, block=2048, backend="jax", wire="staged"):
        self.block = block
        self.backend = backend
        self.wire = wire
        self.name = ("ternary" + ("@kernel" if backend == "kernel" else "")
                     + ("@fused" if wire == "packed" else ""))

    def encode(self, state, rng, x):
        n = x.shape[0]
        if self.backend == "kernel":
            from repro.kernels import ops
            if self.wire == "packed":
                packed, abs_sum = ops.ternarize_signs_packed(x, self.block)
                return {"mu": abs_sum / n, "sign2": packed}, state
            sign, abs_sum = ops.ternarize_signs(x, self.block)
            return {"mu": abs_sum / n, "sign": sign}, state
        mu = jnp.abs(x).mean()
        sign = jnp.sign(x).astype(jnp.int8)
        if self.wire == "packed":
            from repro.compress.wire_format import pack2
            return {"mu": mu, "sign2": pack2(sign)}, state
        return {"mu": mu, "sign": sign}, state

    def decode(self, payload, n):
        if self.wire == "packed":
            from repro.compress.wire_format import unpack2
            sign = unpack2(payload["sign2"], n)
        else:
            sign = payload["sign"]
        return sign.astype(jnp.float32) * payload["mu"]

    def meta_bits(self, n):
        if self.wire == "packed":
            return 8.0 * (-(-n // 4)) + 32.0     # 2-bit packed signs + mu
        return 8.0 * n + 32.0                    # int8 signs + f32 mu

    def meta_entropy_bits(self, n):
        return 1.0 * n + 32.0                    # 1 bit/sign after packing


class SBC(CommTransform):
    """Sattler et al. [69]: binary — keep only the dominant sign's support."""
    biased = True

    def __init__(self, fraction=0.01):
        self.fraction = fraction
        self.name = f"sbc{fraction:g}"

    def encode(self, state, rng, x):
        n = x.shape[0]
        k = _k(n, self.fraction)
        mag, idx = jax.lax.top_k(jnp.abs(x), k)
        v = x[idx]
        pos_sum = jnp.sum(jnp.where(v > 0, v, 0.0))
        neg_sum = -jnp.sum(jnp.where(v < 0, v, 0.0))
        s = jnp.where(pos_sum >= neg_sum, 1.0, -1.0)
        keep = (jnp.sign(v) == s)
        mu = jnp.sum(jnp.abs(v) * keep) / jnp.maximum(keep.sum(), 1)
        # drop the minority-sign entries (their index slot points to 0 weight)
        idx = jnp.where(keep, idx, n)              # n => scatter-dropped
        return {"mu": mu * s, "idx": idx.astype(jnp.int32)}, state

    def decode(self, payload, n):
        out = jnp.zeros((n + 1,), jnp.float32)
        out = out.at[payload["idx"]].set(payload["mu"])
        return out[:n]

    def meta_bits(self, n):
        return _k(n, self.fraction) * 32.0 + 32.0

    def meta_entropy_bits(self, n):
        k = _k(n, self.fraction)
        idx_bits = math.log2(max(n / k, 2.0)) + 2
        return k * idx_bits + 32.0


class RandMask(CommTransform):
    """CPFed [68]: random-mask sparsifier (unbiased, 1/p rescale) with optional
    Gaussian noise on survivors (differential privacy)."""
    biased = False
    carrier_key = "vals"

    def __init__(self, fraction=0.05, dp_sigma=0.0):
        self.fraction = fraction
        self.dp_sigma = dp_sigma
        self.name = f"randmask{fraction:g}"

    def _idx(self, seed_key, n):
        k = _k(n, self.fraction)
        # data-independent mask: pseudo-random permutation from a shared seed
        scores = jax.random.uniform(seed_key, (n,))
        _, idx = jax.lax.top_k(scores, k)
        return idx

    def encode(self, state, rng, x):
        n = x.shape[0]
        seed, noise = jax.random.split(rng)
        idx = self._idx(seed, n)
        vals = x[idx] / self.fraction
        if self.dp_sigma:
            vals = vals + self.dp_sigma * jax.random.normal(noise, vals.shape)
        return {"vals": vals, "seed": seed}, state

    def decode(self, payload, n):
        idx = self._idx(payload["seed"], n)
        out = jnp.zeros((n,), jnp.float32)
        return out.at[idx].set(payload["vals"].astype(jnp.float32))

    def carrier_len(self, n):
        return _k(n, self.fraction)

    def meta_bits(self, n):
        # indices are regenerated from the 64-bit seed — only values travel
        return 64.0


class FusedSTC(CommTransform):
    """``stc@fused`` — the dense packed STC wire format (DESIGN.md §10).

    The staged ``stc`` chain (top-k >> ternary) ships 32-bit indices plus
    8-bit signs per survivor: ``40k + 32`` bits.  This stage ships 2-bit
    ternary codes over the FULL length instead — no indices at all —
    ``8*ceil(n/4) + 32 ≈ 2n`` bits, a strict win whenever the kept
    fraction exceeds ~0.05 (and position-free, so it packs into a plain
    dense collective).  The kernel path is ``ops.stc_ternarize`` end to
    end as ONE pass: threshold -> sign -> 2-bit pack + mu partials, the
    codes never round-tripping HBM (``kernels.bitpack``).

    Support semantics: every |x| >= the k-th magnitude is kept, so exact
    magnitude ties may keep MORE than k coordinates (the staged chain's
    ``top_k`` breaks ties by index) — measure zero on float inputs, and
    the reason fused-vs-staged parity is the bounded-ULP class while the
    kernel-vs-jax parity of this stage is sign-exact."""
    biased = True
    kernel_capable = True
    wire = "packed"

    def __init__(self, fraction=0.01, block=2048, backend="jax"):
        self.fraction = fraction
        self.block = block
        self.backend = backend
        self.name = (f"stc{fraction:g}"
                     + ("@kernel" if backend == "kernel" else "") + "@fused")

    def encode(self, state, rng, x):
        n = x.shape[0]
        if self.backend == "kernel":
            from repro.kernels import ops
            packed, mu = ops.stc_ternarize_packed(x, self.fraction,
                                                  self.block)
            return {"mu": mu, "code2": packed}, state
        from repro.compress.wire_format import pack2
        k = _k(n, self.fraction)
        mag = jnp.abs(x)
        # min over the prefix, not a scalar slice: a slice fused into
        # top_k defeats XLA's TopkRewriter — kernels.ops._stc_threshold
        thresh = jnp.min(jax.lax.top_k(mag, k)[0])
        keep = mag >= thresh
        code = (jnp.sign(x) * keep).astype(jnp.int8)
        mu = jnp.sum(jnp.where(keep, mag, 0.0)) / jnp.maximum(keep.sum(), 1)
        return {"mu": mu, "code2": pack2(code)}, state

    def decode(self, payload, n):
        from repro.compress.wire_format import unpack2
        return unpack2(payload["code2"], n).astype(jnp.float32) * \
            payload["mu"]

    def meta_bits(self, n):
        return 8.0 * (-(-n // 4)) + 32.0         # 2-bit packed codes + mu

    def meta_entropy_bits(self, n):
        # same information as the staged STC chain: k gap-coded positions
        # + 1 sign bit each (run-length over the 2-bit stream); never more
        # than the packed wire itself
        k = _k(n, self.fraction)
        idx_bits = math.log2(max(n / k, 2.0)) + 2
        return min(k * (idx_bits + 1.0) + 32.0, self.meta_bits(n))


def _stc(fraction=0.01, block=2048, backend="jax", wire="staged"):
    if wire == "packed":
        return FusedSTC(fraction, block, backend)
    from repro.compress.pipeline import chain
    return chain(TopK(fraction, block, backend), Ternary(block, backend))


register("topk")(lambda fraction=0.01, block=2048, backend="jax", **kw:
                 TopK(fraction, block, backend))
register("stc")(lambda fraction=0.01, block=2048, backend="jax",
                wire="staged", **kw: _stc(fraction, block, backend, wire))
register("sbc")(lambda fraction=0.01, **kw: SBC(fraction))
register("randmask")(lambda fraction=0.05, dp_sigma=0.0, **kw:
                     RandMask(fraction, dp_sigma))

register_stage("topk")(lambda frac=None, fraction=0.01, block=2048,
                       backend="jax", **kw:
                       TopK(float(frac if frac is not None else fraction),
                            int(block), backend))
register_stage("ternary")(lambda block=2048, backend="jax", wire="staged",
                          **kw: Ternary(int(block), backend, wire))
register_stage("stc")(lambda frac=None, fraction=0.01, block=2048,
                      backend="jax", wire="staged", **kw:
                      _stc(float(frac if frac is not None else fraction),
                           int(block), backend, wire))
register_stage("sbc")(lambda frac=None, fraction=0.01, **kw:
                      SBC(float(frac if frac is not None else fraction)))
register_stage("randmask")(lambda frac=None, fraction=0.05, dp_sigma=0.0, **kw:
                           RandMask(float(frac if frac is not None
                                          else fraction), dp_sigma))
