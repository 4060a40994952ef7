"""Pipeline combinators for the CommTransform protocol (DESIGN.md §2).

``chain(a, b, ...)`` composes stages along each stage's *carrier*: stage i's
``payload[carrier_key]`` is re-encoded by stage i+1 instead of travelling as
f32.  Reconstruction runs the stages backwards, substituting each refined
carrier before the outer decode.  Because only the shrinking carrier is
re-encoded (side info like indices/scales is kept at each stage), wire bits
compose multiplicatively: ``chain(topk(0.01), qsgd(8))`` pays top-k's index
bits on k = 0.01·n coordinates plus QSGD's 8 bits on those k values.

``error_feedback(t)`` / ``momentum_correction(t)`` are *wrapping* transforms
(EF-SGD / DGC): they own the residual / momentum state that previously lived
in ``FLState.ef_residual`` and the trainer, and expose the same protocol, so
the aggregation layer threads state generically with no special cases.

State contract (DESIGN.md §2): every array returned by ``init(shape)`` is
zero-initialised and either leaf-shaped (shards like the parameter it
accompanies) or small; wrappers reshape leaf-shaped state to the flat
working vector internally, so they compose with any inner pipeline.
"""
from __future__ import annotations

import contextlib
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress.api import CommTransform, Identity
from repro.obs import scopes

__all__ = ["Chain", "chain", "ErrorFeedback", "error_feedback",
           "MomentumCorrection", "momentum_correction",
           "stage_sequence", "stage_input_lens", "scoped_encode",
           "scoped_decode"]


class Chain(CommTransform):
    """Sequential composition of stages along their carriers."""

    carrier_key = None          # chains are not themselves chainable stages

    def __init__(self, *stages: CommTransform):
        assert len(stages) >= 2, "use chain(...) — it handles 0/1 stages"
        for s in stages[:-1]:
            if s.carrier_key is None:
                raise ValueError(
                    f"stage {s.name!r} is terminal (no carrier) and cannot "
                    f"be followed by another stage")
        self.stages: Tuple[CommTransform, ...] = tuple(stages)
        self.name = ">>".join(s.name for s in stages)

    @property
    def biased(self):
        return any(s.biased for s in self.stages)

    @property
    def kernel_capable(self):
        return all(s.kernel_capable for s in self.stages)

    def _lens(self, n):
        """Input length seen by each stage: n, then the carrier lengths."""
        ms = [n]
        for s in self.stages[:-1]:
            ms.append(s.carrier_len(ms[-1]))
        return ms

    # --- state -------------------------------------------------------------
    def init(self, shape):
        n = int(np.prod(shape))
        ms = self._lens(n)
        return tuple(s.init(tuple(shape) if i == 0 else (ms[i],))
                     for i, s in enumerate(self.stages))

    # --- wire maps ---------------------------------------------------------
    def encode(self, state, rng, x):
        payload, new_states, cur = {}, [], x
        last = len(self.stages) - 1
        for i, s in enumerate(self.stages):
            p, st = scoped_encode(s, state[i], jax.random.fold_in(rng, i),
                                  cur)
            new_states.append(st)
            if i < last:
                p = dict(p)
                cur = p.pop(s.carrier_key)
            payload[f"s{i}"] = p
        return payload, tuple(new_states)

    def decode(self, payload, n):
        ms = self._lens(n)
        last = len(self.stages) - 1
        cur = scoped_decode(self.stages[last], payload[f"s{last}"], ms[last])
        for i in range(last - 1, -1, -1):
            p = dict(payload[f"s{i}"])
            p[self.stages[i].carrier_key] = cur
            cur = scoped_decode(self.stages[i], p, ms[i])
        return cur

    # --- byte accounting ----------------------------------------------------
    def carrier_len(self, n):
        return self.stages[-1].carrier_len(self._lens(n)[-1])

    def meta_bits(self, n):
        return sum(s.meta_bits(m) for s, m in zip(self.stages, self._lens(n)))

    def dp_rho_per_round(self):
        return sum(s.dp_rho_per_round() for s in self.stages)

    def meta_entropy_bits(self, n):
        # carrier-conditional composition (DESIGN.md §1): each stage's
        # entropy estimate is conditioned on the *distribution* of the
        # carrier it receives (e.g. qsgd levels on a top-k carrier are
        # large, where Elias-gamma is expensive), not just its length
        total, hint = 0.0, None
        for s, m in zip(self.stages, self._lens(n)):
            total += s.meta_entropy_bits_given(m, hint)
            hint = s.carrier_hint(m)
        return total


def _stage_scope(t: CommTransform):
    """The ``stage.<base>`` scope (``repro.obs.scopes``) of a carrier
    stage's work; chains and wrappers (anything with an ``inner``) name
    their own parts, and the identity does no work."""
    if isinstance(t, Chain) or hasattr(t, "inner") or t.is_identity:
        return contextlib.nullcontext()
    return jax.named_scope(scopes.stage(t.name))


def scoped_encode(t: CommTransform, state, rng, x):
    """``t.encode`` under ``t``'s stage scope (HLO metadata only)."""
    with _stage_scope(t):
        return t.encode(state, rng, x)


def scoped_decode(t: CommTransform, payload, n):
    """``t.decode`` under ``t``'s stage scope (HLO metadata only)."""
    with _stage_scope(t):
        return t.decode(payload, n)


def stage_sequence(pipe: CommTransform) -> Tuple[CommTransform, ...]:
    """The carrier stage sequence under any wrappers — the flight recorder's
    per-stage attribution axis (repro.obs.telemetry, DESIGN.md §12).

    Wrappers (EF / DGC momentum, SecAgg, DPNoise) all delegate their byte
    accounting to ``.inner`` (``meta_bits(n) == inner.wire_bits(n)``, no
    carrier of their own), so unwrapping them and decomposing the innermost
    chain reproduces the wrapped pipeline's ``wire_bits`` exactly."""
    while hasattr(pipe, "inner"):
        pipe = pipe.inner
    return tuple(pipe.stages) if isinstance(pipe, Chain) else (pipe,)


def stage_input_lens(stages, n):
    """Input length each stage of a carrier sequence sees for an n-length
    leaf: ``n``, then the preceding carrier lengths (``Chain._lens``)."""
    ms = [n]
    for s in stages[:-1]:
        ms.append(s.carrier_len(ms[-1]))
    return ms


def chain(*transforms: CommTransform) -> CommTransform:
    """Compose transforms; Identity is the unit, a single stage is itself."""
    flat = []
    for t in transforms:
        if isinstance(t, Chain):
            flat.extend(t.stages)
        elif t.is_identity:
            continue
        else:
            flat.append(t)
    if not flat:
        return Identity()
    if len(flat) == 1:
        return flat[0]
    return Chain(*flat)


# ---------------------------------------------------------------------------
# Wrapping transforms — stateful correction schemes as pipeline stages
# ---------------------------------------------------------------------------

class _Wrapper(CommTransform):
    """Shared plumbing: decode and byte accounting delegate to the inner
    pipeline (corrections change *what* is encoded, not the wire format)."""

    biased = False              # the wrapper is the bias correction
    carrier_key = None          # wrappers are outermost, not chainable stages

    def __init__(self, inner: CommTransform):
        self.inner = inner

    def decode(self, payload, n):
        return scoped_decode(self.inner, payload, n)

    def meta_bits(self, n):
        return self.inner.wire_bits(n)

    def meta_entropy_bits(self, n):
        return self.inner.entropy_bits(n)

    def dp_rho_per_round(self):
        return self.inner.dp_rho_per_round()


class ErrorFeedback(_Wrapper):
    """EF-SGD (Karimireddy et al. 2019; the survey's biased-compressor fix):
    encode x + e, keep e' = (x + e) − decode(encode(x + e)) locally."""

    def __init__(self, inner: CommTransform, decay: float = 1.0):
        super().__init__(inner)
        self.decay = decay
        self.name = f"ef({inner.name})"

    def init(self, shape):
        return {"residual": jnp.zeros(shape, jnp.float32),
                "inner": self.inner.init(shape)}

    def encode(self, state, rng, x):
        # stage.ef names the residual arithmetic; the inner stages nest
        with jax.named_scope(scopes.STAGE + "ef"):
            y = x + self.decay * state["residual"].reshape(x.shape)
            payload, ist = scoped_encode(self.inner, state["inner"], rng, y)
            # local decode of our own payload: one extra O(n) dequantize per
            # leaf vs. an aggregator that reuses its post-gather decode — the
            # price of keeping correction state out of the aggregation layer
            y_hat = scoped_decode(self.inner, payload, y.shape[0])
            res = (y - y_hat).reshape(state["residual"].shape)
        return payload, {"residual": res, "inner": ist}


class MomentumCorrection(_Wrapper):
    """DGC (Lin et al. 2018) momentum correction + gradient accumulation:
    u ← m·u + x; v ← v + u; transmit encode(v); the unsent part of v stays
    local and the momentum of *sent* coordinates is cleared (masking).

    Warm-up sparsity schedule (DGC §3.3): with ``warmup_rounds = W`` and
    ``final_fraction = f``, round r transmits the top ``f^((r+1)/(W+1))``
    fraction — exponentially annealing from nearly-dense to the target.
    Shapes stay static under jit: the *inner* pipeline is sized for the
    first (widest) round's fraction and later rounds mask ``v`` down to the
    annealed effective support before encoding, so the extra slots carry
    zeros. The wire payload (and ``wire_bits``) is therefore constant at
    the warm-up capacity; the *effective* sparsity anneals."""

    def __init__(self, inner: CommTransform, momentum: float = 0.9,
                 warmup_rounds: int = 0, final_fraction: float = 0.0):
        super().__init__(inner)
        self.momentum = momentum
        self.warmup_rounds = int(warmup_rounds)
        self.final_fraction = final_fraction
        self.name = f"mc{momentum:g}({inner.name})"
        if self.warmup_rounds:
            assert 0.0 < final_fraction <= 1.0, \
                "warm-up schedule needs the target (final) fraction"
            self.name += f"@warmup{self.warmup_rounds}"

    def init(self, shape):
        st = {"u": jnp.zeros(shape, jnp.float32),
              "v": jnp.zeros(shape, jnp.float32),
              "inner": self.inner.init(shape)}
        if self.warmup_rounds:
            st["round"] = jnp.zeros((), jnp.int32)
        return st

    def _anneal_mask(self, v, rounds):
        """Zero all but the top-k_eff coordinates of v, where the effective
        fraction f_r = final^((r+1)/(W+1)) anneals down to final.

        k_eff is traced (``rounds`` is carried state) but bounded by the
        schedule's STATIC round-0 fraction final^(1/(W+1)), so one
        ``lax.top_k`` over that widest prefix replaces a full sort and the
        order statistic is gathered from the prefix — the same
        construction as ``kernels.ops.stc_ternarize(max_fraction=...)``."""
        n = v.shape[0]
        expo = jnp.minimum(rounds + 1, self.warmup_rounds + 1) / \
            (self.warmup_rounds + 1.0)
        frac = jnp.exp(expo * jnp.log(self.final_fraction))
        k_eff = jnp.clip(jnp.round(n * frac).astype(jnp.int32), 1, n)
        f_max = self.final_fraction ** (1.0 / (self.warmup_rounds + 1.0))
        k_max = max(1, min(int(round(n * f_max)), n))
        # masked min, not a gather: a slice/gather fused into top_k's
        # output defeats XLA's TopkRewriter (full-sort fallback) — see
        # kernels.ops._stc_threshold
        prefix = jax.lax.top_k(jnp.abs(v), k_max)[0]
        thr = jnp.min(jnp.where(jnp.arange(k_max) < jnp.minimum(k_eff, k_max),
                                prefix, jnp.inf))
        return jnp.where(jnp.abs(v) >= thr, v, 0.0)

    def encode(self, state, rng, x):
        with jax.named_scope(scopes.STAGE + "dgc"):
            u = self.momentum * state["u"].reshape(x.shape) + x
            v = state["v"].reshape(x.shape) + u
            v_enc = v
            if self.warmup_rounds:
                v_enc = self._anneal_mask(v, state["round"])
            payload, ist = scoped_encode(self.inner, state["inner"], rng,
                                         v_enc)
            v_hat = scoped_decode(self.inner, payload, v.shape[0])
            sent = v_hat != 0.0
            new_v = (v - v_hat).reshape(state["v"].shape)
            new_u = jnp.where(sent, 0.0, u).reshape(state["u"].shape)
        new_state = {"u": new_u, "v": new_v, "inner": ist}
        if self.warmup_rounds:
            new_state["round"] = state["round"] + 1
        return payload, new_state


def error_feedback(inner: CommTransform, decay: float = 1.0) -> CommTransform:
    return ErrorFeedback(inner, decay)


def momentum_correction(inner: CommTransform, momentum: float = 0.9,
                        warmup_rounds: int = 0,
                        final_fraction: float = 0.0) -> CommTransform:
    return MomentumCorrection(inner, momentum, warmup_rounds, final_fraction)
