"""Property-based tests of the compression substrate.

Fuzzed properties use ``hypothesis`` when it is installed; without it each
fuzzed test degrades to a fixed-seed parametrized sweep so the core
round-trip/error-bound assertions still run (the CI image pins hypothesis,
minimal images may not have it).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis  # noqa: F401 — probe only; see `fuzz` below
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.compress import make_compressor
from repro.compress.sketch import sketch, unsketch

ALL = ["none", "qsgd8", "qsgd4", "uveq", "hsq", "topk", "stc", "sbc",
       "randmask", "sketch"]
UNBIASED = ["none", "qsgd8", "qsgd4", "uveq", "randmask"]


def fuzz(*strategies, fallback, max_examples=20):
    """``@given(*strategies)`` under hypothesis; fixed-example parametrize
    otherwise. ``fallback`` is a list of argument tuples."""
    def deco(fn):
        if HAVE_HYPOTHESIS:
            return settings(max_examples=max_examples, deadline=None)(
                given(*strategies)(fn))
        nargs = fn.__code__.co_argcount
        argnames = ",".join(fn.__code__.co_varnames[:nargs])
        vals = [t[0] for t in fallback] if nargs == 1 else fallback
        return pytest.mark.parametrize(argnames, vals)(fn)
    return deco


def _st(builder):
    """Build a strategy lazily so module import never touches hypothesis."""
    return builder() if HAVE_HYPOTHESIS else None


def _x(seed, n, scale):
    return jax.random.normal(jax.random.PRNGKey(seed), (n,)) * scale


@pytest.mark.parametrize("name", ALL)
def test_roundtrip_shape_dtype(name):
    comp = make_compressor(name, fraction=0.05, cols=512)
    x = _x(0, 3000, 2.0)
    y = comp.roundtrip(jax.random.PRNGKey(1), x)
    assert y.shape == x.shape
    assert y.dtype == jnp.float32
    assert bool(jnp.isfinite(y).all())


@pytest.mark.parametrize("name", UNBIASED)
def test_unbiasedness(name):
    """E[Q(x)] = x for the stochastic quantizers / 1/p-rescaled masks."""
    comp = make_compressor(name, fraction=0.25, block=256)
    x = _x(2, 512, 1.0)
    reps = 300
    acc = jnp.zeros_like(x)
    for i in range(reps):
        acc = acc + comp.roundtrip(jax.random.PRNGKey(i), x)
    mean = acc / reps
    err = float(jnp.abs(mean - x).mean()) / float(jnp.abs(x).mean())
    assert err < 0.1, (name, err)


@pytest.mark.parametrize("name", ["topk", "stc", "sbc", "hsq"])
def test_biased_flagged_for_error_feedback(name):
    assert make_compressor(name).biased


@pytest.mark.parametrize("name", ALL)
def test_wire_bits_monotone_and_saving(name):
    comp = make_compressor(name, fraction=0.01, cols=512)
    assert comp.wire_bits(1 << 20) >= comp.wire_bits(1 << 10) or name == "sketch"
    if name not in ("none",):
        assert comp.wire_bits(1 << 20) < 32.0 * (1 << 20)  # beats dense f32
    assert comp.entropy_bits(1 << 20) <= comp.wire_bits(1 << 20) + 1e-6


def test_topk_keeps_largest():
    comp = make_compressor("topk", fraction=0.01)
    x = _x(3, 1000, 1.0).at[7].set(100.0)
    y = comp.roundtrip(jax.random.PRNGKey(0), x)
    assert float(y[7]) == 100.0


def test_stc_ternary_levels():
    comp = make_compressor("stc", fraction=0.1)
    x = _x(4, 1000, 3.0)
    y = np.asarray(comp.roundtrip(jax.random.PRNGKey(0), x))
    vals = np.unique(np.abs(y[y != 0]))
    assert len(vals) == 1          # single magnitude mu
    assert int((y != 0).sum()) >= 100


def test_sbc_single_sign():
    comp = make_compressor("sbc", fraction=0.1)
    x = _x(5, 1000, 1.0)
    y = np.asarray(comp.roundtrip(jax.random.PRNGKey(0), x))
    nz = y[y != 0]
    assert len(np.unique(nz)) == 1  # one signed magnitude only


@fuzz(_st(lambda: st.integers(0, 2**31 - 1)), _st(lambda: st.floats(0.1, 10.0)),
      fallback=[(0, 0.1), (1, 1.0), (7, 3.3), (123, 10.0), (999, 0.5)])
def test_qsgd_error_bounded_by_block_scale(seed, scale):
    """|x - Q(x)| <= scale_block / levels per coordinate (QSGD guarantee)."""
    comp = make_compressor("qsgd8", block=128)
    x = _x(seed % 1000, 512, scale)
    y = comp.roundtrip(jax.random.PRNGKey(seed % 997), x)
    xb = np.asarray(x).reshape(4, 128)
    errb = np.asarray(y - x).reshape(4, 128)
    for b in range(4):
        bound = np.abs(xb[b]).max() / 127 + 1e-6
        assert np.abs(errb[b]).max() <= bound + 1e-5


@fuzz(_st(lambda: st.integers(0, 10_000)),
      fallback=[(0,), (17,), (512,), (4095,), (9999,)], max_examples=15)
def test_sketch_linearity(seed):
    """sketch(a + b) == sketch(a) + sketch(b) — what lets FetchSGD aggregate
    sketches server-side."""
    a = _x(seed, 2048, 1.0)
    b = _x(seed + 1, 2048, 2.0)
    Sa = sketch(a, 5, 256)
    Sb = sketch(b, 5, 256)
    Sab = sketch(a + b, 5, 256)
    np.testing.assert_allclose(np.asarray(Sa + Sb), np.asarray(Sab),
                               rtol=1e-4, atol=1e-4)


def test_error_feedback_contraction():
    """The EF residual of top-k stays bounded: ||e_t|| <= (1-k/n)·growth."""
    comp = make_compressor("topk", fraction=0.1)
    n = 1000
    e = jnp.zeros((n,))
    norms = []
    for t in range(30):
        g = _x(t, n, 1.0)
        target = g + e
        q = comp.roundtrip(jax.random.PRNGKey(t), target)
        e = target - q
        norms.append(float(jnp.linalg.norm(e)))
    # residual norm must stabilise (contraction), not blow up
    assert max(norms[10:]) < 3.0 * np.mean(norms[:5])


@fuzz(_st(lambda: st.integers(2, 64)),
      fallback=[(2,), (5,), (13,), (40,), (64,)], max_examples=10)
def test_randmask_deterministic_given_seed(k):
    comp = make_compressor("randmask", fraction=0.2)
    x = _x(k, 256, 1.0)
    p1 = comp.compress(jax.random.PRNGKey(k), x)
    y1 = comp.decompress(p1, 256)
    y2 = comp.decompress(p1, 256)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def _gauss2(n, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, n))


def _half_zero(n):
    x = _gauss2(n).at[:, ::2].set(0.0).at[0, 1::4].set(-0.0)
    few = jnp.where(jnp.arange(x[:, 1::2].shape[1]) < 10, 1.0, 0.0)
    return x.at[:, 1::2].set(few)        # 10 non-zeros per row, k > 10


# (input, k, descent chunk): ties at the threshold, ties at zero, signed
# zeros and infinities, both ends of k, n off the 128-lane rows, and two
# count levels walked in several chunks
N = 3001
SELECT_CASES = {
    "gaussian": (lambda: _gauss2(N), 40, None),
    "quantized": (lambda: jnp.round(_gauss2(N) * 2.0) / 2.0, 40, None),
    "half_zero": (lambda: _half_zero(N), 40, None),
    "zero_inf": (lambda: _gauss2(N).at[0, 5].set(jnp.inf)
                 .at[1, 7].set(-jnp.inf).at[:, 9].set(-0.0)
                 .at[:, 11].set(0.0), 40, None),
    "k1": (lambda: _gauss2(N), 1, None),
    "kn": (lambda: jnp.round(_gauss2(N)), N, None),
    "levels_chunks": (lambda: jnp.round(_gauss2(20_001) * 4.0) / 4.0,
                      1000, 256),
}


@functools.partial(jax.jit, static_argnums=1)
def _select(x, k):
    from repro.compress.sparsification import _topk_select
    return jax.vmap(lambda v: _topk_select(v, k))(x)


@pytest.mark.parametrize("case", list(SELECT_CASES))
def test_topk_select_bit_identical_to_lax_top_k(case, monkeypatch):
    """The sort-free selection returns ``lax.top_k``'s indices in its order
    (descending magnitude, ties by ascending index) and x at them, bit for
    bit, vmapped over two clients as the engine calls it."""
    from repro.compress import sparsification
    make, k, chunk = SELECT_CASES[case]
    if chunk:
        monkeypatch.setattr(sparsification, "CHUNK", chunk)
    x = make()
    vals, idx = _select(x, k)
    want = jax.vmap(lambda v: jax.lax.top_k(jnp.abs(v), k)[1])(x)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(vals).view(np.int32),
        np.asarray(jnp.take_along_axis(x, want, axis=1)).view(np.int32))
