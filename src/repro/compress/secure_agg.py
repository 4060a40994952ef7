"""Privacy stages for the wire stack (DESIGN.md §11).

Two wrapping ``CommTransform``s close the ROADMAP "privacy-compatible wire
stack" item by riding the *existing* grammar, ledger and state threading —
privacy is a pipeline property here, not a side channel:

``secagg`` — secure-aggregation-shaped masking over the **integer code
domain** of a quantizing pipeline.  Each client adds a pairwise modular mask
to every integer payload plane (int8 QSGD levels, 2-bit-packed ternary
bytes, top-k indices, ...) before the plane crosses the collective, and the
cohort's masks cancel *exactly*:

    m_i = g(i) - g((i-1) mod C)   over  Z_{2^w}  (w = plane dtype width)

with ``g(e) = PRG(fold_in(mask_key, e))`` a full-entropy draw per ring edge.
The sum over any full cohort telescopes to 0 mod 2^w, so the *sum of masked
code planes equals the sum of clear code planes bit-for-bit* — no float
arithmetic is involved, only two's-complement adds that XLA defines as
wraparound.  Each client touches O(1) PRG draws (its two ring edges), the
mask shape equals the plane shape, and a masked uint8 plane still all-gathers
as uint8 — composition with the PR 7 packed wire formats is free.

The mask context (shared per-round key, client index, cohort size) travels
through ``FLState.comm_state`` like any pipeline state; every wire hop
(sim/async dispatch, star shard_map, hier edge, gossip mix) injects its own
(key, idx, cohort) via :func:`inject_mask_ctx` before encoding.  The context
also rides in the payload (``secagg_ctx``) so the aggregator can re-derive
and subtract the mask per client — the simulation stand-in for SecAgg's
key-agreement channel (Bonawitz et al.), exactly as UVeQ ships its dither
seed.  The 128 ctx bits per leaf are *not* billed to ``wire_bits`` (a real
deployment establishes keys out of band, amortised over rounds); the payload
therefore measures ``wire_bits/8 + CTX_BITS/8`` bytes, a relation the tests
pin down.

``secagg`` refuses float carriers: masking is a group operation over Z_{2^w},
and an f32 plane has no modular group to cancel in.  Chain a quantizing
carrier first (``"qsgd:4>>secagg"``, ``"topk:0.05>>qsgd:4>>secagg"``).

``dpnoise:<sigma>[,<clip>]`` — client-level DP at the wire boundary: ``clip``
bounds the L2 norm of the **whole per-client update** (all leaves jointly),
and each of the model's L leaves gets an equal share ``clip/sqrt(L)`` of
that budget (encode runs per leaf, so the split is how a per-leaf transform
realises a joint sensitivity bound).  Every leaf is then perturbed with
N(0, (sigma*clip)^2) — the Gaussian mechanism in noise-multiplier form over
the joint release — before the noised update reaches the inner pipeline.
The leaf count is bound by the engine at build time (:func:`bind_n_leaves`,
called from ``ledger_terms`` / the hier and gossip builders); unbound
standalone use defaults to L = 1, the single-leaf case where split and
no-split coincide.  The inner pipeline's rng stream is passed through
*unmodified*, so ``sigma=0, clip=inf`` is a bit-exact no-op.  Privacy
accounting is zCDP: the joint sensitivity is sqrt(sum_l (clip/sqrt(L))^2)
= clip and the noise std is sigma*clip, so rho = 1/(2 sigma^2) per client
per round — independent of the leaf count *because* the clip budget is
split, not by assumption.  rho threads through ``CommLedger`` (``dp_rho``)
by the same additive accumulation as bytes — zCDP composes additively, so
the running ledger *is* the privacy budget.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress.api import CommTransform, Payload, PyTree
from repro.compress.pipeline import scoped_decode, scoped_encode
from repro.obs import scopes

__all__ = ["SecAgg", "DPNoise", "PRIVACY_STAGES", "make_privacy_stage",
           "has_mask_ctx", "inject_mask_ctx", "drop_mask_ctx", "ring_mask",
           "mask_payload", "dropout_correction", "zcdp_epsilon",
           "bind_n_leaves", "MASK_TAG", "DP_TAG", "CTX_BITS"]

PRIVACY_STAGES = ("secagg", "dpnoise")

MASK_TAG = 0x5eca66      # folds the round key into the shared mask-key stream
DP_TAG = 0xd9015e        # folds the per-client rng into the DP noise stream
CTX_BITS = 128           # per-leaf secagg_ctx: key u32[2] + idx i32 + cohort i32

_PROBE_N = 4096          # carrier probe length for the construction-time guard


# ---------------------------------------------------------------------------
# Mask algebra over Z_{2^w}
# ---------------------------------------------------------------------------

def _edge_draw(key, edge, ref):
    """Full-entropy uniform draw over the unsigned group of ``ref``'s width
    for ring edge ``edge`` (traced or static)."""
    w = 8 * ref.dtype.itemsize
    return jax.random.bits(jax.random.fold_in(key, edge), ref.shape,
                           jnp.dtype(f"uint{w}"))


def ring_mask(key, idx, cohort, ref):
    """Client ``idx``'s pairwise mask m_i = g(i) - g((i-1) mod C) in the
    dtype of ``ref``.  Sum over idx = 0..C-1 telescopes to 0 mod 2^w.
    ``cohort < 2`` (including the uninjected zero context) yields a zero
    mask, so standalone pipeline use is transparently unmasked."""
    coh = jnp.maximum(jnp.asarray(cohort, jnp.int32), 1)
    i = jnp.asarray(idx, jnp.int32) % coh
    prev = (i + coh - 1) % coh
    m = _edge_draw(key, i, ref) - _edge_draw(key, prev, ref)
    m = jnp.where(coh >= 2, m, jnp.zeros_like(m))
    if m.dtype != ref.dtype:
        m = jax.lax.bitcast_convert_type(m, ref.dtype)
    return m


def _map_int_leaves(tree, fn):
    """Apply ``fn(plane_id, leaf)`` to every integer-dtype leaf, in the
    stable tree-flatten order (the plane id both sides of the wire agree on)."""
    leaves, treedef = jax.tree.flatten(tree)
    out = [fn(i, leaf)
           if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.integer) else leaf
           for i, leaf in enumerate(leaves)]
    return jax.tree.unflatten(treedef, out)


def mask_payload(body, key, idx, cohort, sign):
    """Add (sign=+1) or subtract (sign=-1) the ring mask on every integer
    plane of a payload tree.  Integer add/sub in XLA wraps two's-complement,
    which *is* the group operation of Z_{2^w} — cancellation is exact, never
    approximate.  Float side info (scales, mu) is left clear; it carries no
    per-coordinate information once the codes are masked."""
    def one(i, leaf):
        m = ring_mask(jax.random.fold_in(key, i), idx, cohort, leaf)
        return leaf + m if sign > 0 else leaf - m
    return _map_int_leaves(body, one)


def dropout_correction(key, drop_idx, cohort, template):
    """The dropped client's mask tree m_d over ``template``'s integer planes.

    Mask-recovery semantics (satellite: dropout-of-one): a code-plane sum
    over a cohort missing client d equals the clear sum *minus* m_d (the
    other C-1 masks telescope to -m_d), so adding this tree back restores
    bit-exactness — the simulation analogue of SecAgg's seed-recovery round.
    """
    def one(i, leaf):
        return ring_mask(jax.random.fold_in(key, i), drop_idx, cohort, leaf)
    return _map_int_leaves(template, one)


def zcdp_epsilon(rho, delta=1e-5):
    """Convert cumulative zCDP rho to (epsilon, delta)-DP."""
    rho = float(rho)
    if rho <= 0.0:
        return 0.0
    if not math.isfinite(rho):
        return float("inf")
    return rho + 2.0 * math.sqrt(rho * math.log(1.0 / delta))


# ---------------------------------------------------------------------------
# Mask-context threading helpers (used by the engine wire hops)
# ---------------------------------------------------------------------------

def has_mask_ctx(pipe) -> bool:
    """True if the pipeline contains a SecAgg stage anywhere (so a wire hop
    must inject (key, idx, cohort) into the comm state before encoding)."""
    if isinstance(pipe, SecAgg):
        return True
    stages = getattr(pipe, "stages", None)
    if stages is not None:
        return any(has_mask_ctx(s) for s in stages)
    inner = getattr(pipe, "inner", None)
    return has_mask_ctx(inner) if inner is not None else False


def bind_n_leaves(pipe, n_leaves: int) -> int:
    """Tell every DPNoise stage inside ``pipe`` how many parameter leaves
    the model it encodes has, so the per-leaf clip share ``clip/sqrt(L)``
    keeps the *joint* update sensitivity at ``clip`` (and the billed
    rho = 0.5/sigma^2 honest).  Engine builders call this once per build,
    before any trace; returns the number of stages bound."""
    n_leaves = int(n_leaves)
    if n_leaves < 1:
        raise ValueError(f"n_leaves must be >= 1, got {n_leaves}")
    if isinstance(pipe, DPNoise):
        pipe.n_leaves = n_leaves
        return 1 + bind_n_leaves(pipe.inner, n_leaves)
    bound = 0
    stages = getattr(pipe, "stages", None)
    if stages is not None:
        bound += sum(bind_n_leaves(s, n_leaves) for s in stages)
    inner = getattr(pipe, "inner", None)
    if inner is not None:
        bound += bind_n_leaves(inner, n_leaves)
    return bound


def inject_mask_ctx(state, key, idx, cohort):
    """Rewrite every SecAgg mask context in a comm-state tree (static Python
    recursion — structure is trace-time constant; key/idx/cohort may be
    traced).  States without a context pass through unchanged."""
    if isinstance(state, dict):
        out = {k: inject_mask_ctx(v, key, idx, cohort)
               for k, v in state.items()}
        if "mask_key" in out:
            out["mask_key"] = jnp.asarray(key, jnp.uint32)
            out["mask_idx"] = jnp.asarray(idx, jnp.int32).reshape(())
            out["mask_cohort"] = jnp.asarray(cohort, jnp.int32).reshape(())
        return out
    if isinstance(state, (tuple, list)):
        return type(state)(inject_mask_ctx(v, key, idx, cohort)
                           for v in state)
    return state


def drop_mask_ctx(state):
    """Strip SecAgg context entries from a comm-state tree, recovering the
    tree an *unmasked* pipeline would hold — the masked-vs-unmasked
    differential harness compares the survivors leaf-for-leaf."""
    if isinstance(state, dict):
        if "mask_key" in state:
            return drop_mask_ctx(state["inner"])
        return {k: drop_mask_ctx(v) for k, v in state.items()}
    if isinstance(state, (tuple, list)):
        return type(state)(drop_mask_ctx(v) for v in state)
    return state


# ---------------------------------------------------------------------------
# The stages
# ---------------------------------------------------------------------------

class SecAgg(CommTransform):
    """Pairwise-mask the integer code planes of ``inner``'s payload.

    Wrapping transform (like EF/DGC): decode re-derives the mask from the
    payload's ``secagg_ctx`` and subtracts it, so the aggregation layer's
    decode-per-client-then-weighted-mean structure needs no special cases,
    and a zero-weight (dropped-out) client can never corrupt the mean.
    Byte accounting delegates to ``inner`` unchanged — masking costs zero
    wire bytes — but ``entropy_bits`` collapses to ``wire_bits``: masked
    codes are uniform on Z_{2^w}, so the source papers' entropy coders can
    no longer compress them.  That loss is the honest price of masking and
    the tests pin it down.
    """

    carrier_key = None        # wrapping transform, not a chainable stage

    def __init__(self, inner: CommTransform):
        if has_mask_ctx(inner):
            raise ValueError("secagg is already in this pipeline; "
                             "masks are applied once, at the outermost "
                             "integer code domain")
        if inner.carrier_len(_PROBE_N) > 0:
            raise ValueError(
                f"secagg masks integer code domains, but {inner.name!r} "
                f"leaves a float32 carrier on the wire — chain a quantizing "
                f"carrier before secagg (e.g. 'qsgd:4>>secagg', "
                f"'topk:0.05>>qsgd:4>>secagg', 'ternary>>secagg')")
        self.inner = inner
        self.name = f"{inner.name}>>secagg"

    # masking changes neither bias nor backend/wire capabilities
    @property
    def biased(self):
        return self.inner.biased

    @property
    def kernel_capable(self):
        return self.inner.kernel_capable

    @property
    def wire(self):
        return self.inner.wire

    @property
    def backend(self):
        return self.inner.backend

    def init(self, shape):
        return {"mask_key": jnp.zeros((2,), jnp.uint32),
                "mask_idx": jnp.zeros((), jnp.int32),
                "mask_cohort": jnp.zeros((), jnp.int32),
                "inner": self.inner.init(shape)}

    def encode(self, state, rng, x):
        with jax.named_scope(scopes.STAGE + "secagg"):
            # the inner pipeline sees the rng stream unmodified — masked
            # and unmasked runs draw identical quantization randomness
            payload, ist = scoped_encode(self.inner, state["inner"], rng, x)
            key, idx, coh = (state["mask_key"], state["mask_idx"],
                             state["mask_cohort"])
            out = dict(mask_payload(payload, key, idx, coh, +1))
        out["secagg_ctx"] = {"key": key, "idx": idx, "cohort": coh}
        return out, dict(state, inner=ist)

    def decode(self, payload: Payload, n: int):
        with jax.named_scope(scopes.STAGE + "secagg"):
            p = dict(payload)
            ctx = p.pop("secagg_ctx")
            body = mask_payload(p, ctx["key"], ctx["idx"], ctx["cohort"], -1)
            return scoped_decode(self.inner, body, n)

    # --- byte accounting: ctx is the out-of-band key channel, unbilled ----
    def meta_bits(self, n):
        return self.inner.wire_bits(n)

    def meta_entropy_bits(self, n):
        return self.inner.wire_bits(n)   # masked planes are incompressible

    def dp_rho_per_round(self):
        return self.inner.dp_rho_per_round()


class DPNoise(CommTransform):
    """Client-level clip + Gaussian noise ahead of ``inner``'s encode.

    ``clip`` is the L2 budget of the WHOLE per-client update.  Encode runs
    per leaf, so each of the model's ``n_leaves`` leaves is clipped to its
    equal share ``clip/sqrt(n_leaves)`` and perturbed with std sigma*clip;
    the joint release is then one Gaussian mechanism with sensitivity
    sqrt(sum_l (clip/sqrt(L))^2) = clip and noise multiplier sigma, so rho
    per round is 1/(2 sigma^2) — leaf-count independent *because* the clip
    budget is split (without the split, L independently-clipped leaves
    would compose to L x 0.5/sigma^2).  ``n_leaves`` is bound by the
    engine via :func:`bind_n_leaves`; the default 1 is exact for
    single-leaf use, where split and no-split coincide.  State, decode and
    byte accounting are the inner pipeline's verbatim; with ``sigma == 0``
    and an infinite clip both branches vanish statically and the transform
    is a bit-exact no-op (the inner rng stream is untouched).
    """

    carrier_key = None

    def __init__(self, inner: CommTransform, sigma: float, clip: float = 1.0):
        sigma, clip = float(sigma), float(clip)
        if sigma < 0.0:
            raise ValueError(f"dpnoise sigma must be >= 0, got {sigma}")
        if clip <= 0.0:
            raise ValueError(f"dpnoise clip must be > 0 (use inf to disable "
                             f"clipping), got {clip}")
        if sigma > 0.0 and not math.isfinite(clip):
            raise ValueError("dpnoise with sigma > 0 needs a finite clip — "
                             "unbounded sensitivity has no DP guarantee")
        self.inner = inner
        self.sigma = sigma
        self.clip = clip
        self.n_leaves = 1            # rebound per model via bind_n_leaves
        self.name = f"{inner.name}>>dpnoise:{sigma:g}" + \
            (f",{clip:g}" if clip != 1.0 else "")

    @property
    def biased(self):
        return self.inner.biased

    @property
    def kernel_capable(self):
        return self.inner.kernel_capable

    @property
    def wire(self):
        return self.inner.wire

    @property
    def backend(self):
        return self.inner.backend

    def init(self, shape):
        return self.inner.init(shape)

    def encode(self, state, rng, x):
        with jax.named_scope(scopes.STAGE + "dpnoise"):
            y = x
            if math.isfinite(self.clip):
                # this leaf's equal share of the joint L2 budget: clipping
                # each of L leaves to clip/sqrt(L) bounds the update to clip
                leaf_clip = self.clip / math.sqrt(self.n_leaves)
                nrm = jnp.linalg.norm(y)
                y = y * jnp.minimum(1.0, leaf_clip / jnp.maximum(nrm, 1e-12))
            if self.sigma > 0.0:
                # std is sigma x the JOINT sensitivity (clip, not
                # leaf_clip): the L-leaf release is one Gaussian mechanism
                # at rho=0.5/sigma^2
                z = jax.random.normal(jax.random.fold_in(rng, DP_TAG),
                                      y.shape, y.dtype)
                y = y + jnp.asarray(self.sigma * self.clip, y.dtype) * z
            return scoped_encode(self.inner, state, rng, y)

    def decode(self, payload, n):
        return scoped_decode(self.inner, payload, n)

    def meta_bits(self, n):
        return self.inner.wire_bits(n)

    def meta_entropy_bits(self, n):
        return self.inner.entropy_bits(n)

    def dp_rho_per_round(self):
        if self.sigma == 0.0:
            return self.inner.dp_rho_per_round()
        return 0.5 / (self.sigma * self.sigma) + \
            self.inner.dp_rho_per_round()


# ---------------------------------------------------------------------------
# Spec-grammar hook (consumed by api.make_compressor)
# ---------------------------------------------------------------------------

def make_privacy_stage(token: str, inner: CommTransform,
                       **kw) -> CommTransform:
    """Wrap ``inner`` with the privacy stage named by a spec token
    (``"secagg"``, ``"dpnoise:0.8"``, ``"dpnoise:0.8,1.0"``; a second ``:``
    is accepted as the clip separator)."""
    token = token.strip()
    if "@" in token:
        raise ValueError(
            f"privacy stage {token!r} takes no @suffix — put @kernel/@fused "
            f"on the carrier stages (e.g. 'ternary@fused>>secagg')")
    name, _, argstr = token.partition(":")
    name = name.strip()
    args = [float(a) for a in argstr.replace(":", ",").split(",")
            if a.strip()] if argstr else []
    if name == "secagg":
        if args:
            raise ValueError(f"secagg takes no args, got {token!r}")
        return SecAgg(inner)
    if name == "dpnoise":
        if not args:
            raise ValueError("dpnoise needs a sigma: 'dpnoise:<sigma>"
                             "[,<clip>]' (clip defaults to 1.0)")
        clip = args[1] if len(args) > 1 else float(kw.get("dp_clip", 1.0))
        return DPNoise(inner, args[0], clip)
    raise KeyError(f"unknown privacy stage {token!r}; have {PRIVACY_STAGES}")
