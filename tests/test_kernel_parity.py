"""Differential parity harness: kernel backend vs pure JAX (DESIGN.md §6).

Every kernel-capable stage, every combined-sweep chain, and the EF/DGC
wrappers run through BOTH backends on identical inputs (tests/parity_cases
table). Assertions per case:

  * decoded payloads match — bit-exact for the deterministic layouts,
    bounded-tolerance where padding/blocking reorders a reduction;
  * comm_state (EF residual / DGC momentum / warm-up counter) evolves
    identically across rounds;
  * ledger byte counts (`wire_bits` / `entropy_bits`) are identical —
    kernel-layout padding never reaches the ledger.

Runs in Pallas interpret mode on CPU CI; the same table validates on real
TPU unchanged (`repro.kernels.ops._interpret` switches on the backend).

Also here: the `_to_blocked` padding property tests (hypothesis-optional
with fixed-seed fallbacks, per tests/test_compressors.py convention) and
the engine-level `FLConfig.backend` threading checks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import make_compressor
from repro.kernels import ops

# the hypothesis-optional fuzz helper is shared with the compressor suite
from test_compressors import HAVE_HYPOTHESIS, _st, fuzz
from parity_cases import ALL_CASES, INPUTS, build

if HAVE_HYPOTHESIS:
    from hypothesis import strategies as st

IDS = [c["name"] for c in ALL_CASES]


def _assert_close(a, b, exact, tol, what):
    a, b = np.asarray(a), np.asarray(b)
    if exact or a.dtype.kind in "iub":
        np.testing.assert_array_equal(a, b, err_msg=what)
    else:
        scale = max(float(np.abs(a).max()), 1e-6)
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol * scale,
                                   err_msg=what)


# ---------------------------------------------------------------------------
# The differential harness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c", ALL_CASES, ids=IDS)
def test_backend_parity(c):
    input_fn = INPUTS[c["input"]]
    pure = build(c, "jax")
    kern = build(c, "kernel")
    for n in c["sizes"]:
        # --- ledger: identical byte counts, pad lanes never billed --------
        assert kern.wire_bits(n) == pure.wire_bits(n), (c["name"], n)
        assert kern.entropy_bits(n) == pure.entropy_bits(n), (c["name"], n)

        st_p, st_k = pure.init((n,)), kern.init((n,))
        for r in range(c["rounds"]):
            x = input_fn(1000 * r + n, n)
            rng = jax.random.fold_in(jax.random.PRNGKey(7), r)
            pay_p, st_p = pure.encode(st_p, rng, x)
            pay_k, st_k = kern.encode(st_k, rng, x)
            # layout contract: kernel payload SHAPES equal the pure path's
            # (what crosses the collectives — grid padding never ships)
            assert jax.tree.map(jnp.shape, pay_k) == \
                jax.tree.map(jnp.shape, pay_p), (c["name"], n, r)
            y_p = pure.decode(pay_p, n)
            y_k = kern.decode(pay_k, n)
            _assert_close(y_p, y_k, c["exact"], c["tol"],
                          f"{c['name']} n={n} round={r}: decoded payload")
            # support parity holds even for the tolerance classes: a
            # reduction reorder may move mu by ULPs, never the mask
            np.testing.assert_array_equal(
                np.asarray(y_p) == 0, np.asarray(y_k) == 0,
                err_msg=f"{c['name']} n={n} round={r}: support")
            for lp, lk in zip(jax.tree.leaves(st_p), jax.tree.leaves(st_k)):
                _assert_close(lp, lk, c["exact"], c["tol"],
                              f"{c['name']} n={n} round={r}: comm_state")


def test_kernel_names_tagged():
    """`@kernel` stages are visible in the pipeline name (debuggability)."""
    assert make_compressor("qsgd:8", backend="kernel").name == "qsgd8@kernel"
    assert make_compressor("topk:0.01@kernel>>qsgd:8").name == \
        "topk0.01@kernel>>qsgd8"


def test_explicit_kernel_on_uncapable_stage_fails():
    for spec in ("hsq@kernel", "sbc:0.01@kernel", "randmask:0.05@kernel",
                 "uveq:4@kernel"):
        with pytest.raises(ValueError, match="no kernel backend"):
            make_compressor(spec)
    # ...but the global backend kwarg degrades gracefully to pure JAX
    assert make_compressor("hsq", backend="kernel").name == "hsq"


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown backend"):
        make_compressor("qsgd:8@gpu")
    with pytest.raises(ValueError, match="unknown backend"):
        make_compressor("qsgd:8", backend="tpu")


# ---------------------------------------------------------------------------
# _to_blocked padding properties (satellite: arbitrary n vs block/ROWS)
# ---------------------------------------------------------------------------

@fuzz(_st(lambda: st.integers(1, 40_000)),
      _st(lambda: st.sampled_from([128, 256, 512, 2048])),
      fallback=[(1, 128), (100, 256), (2048, 2048), (2049, 128),
                (4096, 512), (5000, 2048), (8 * 2048, 2048),
                (8 * 2048 + 1, 2048)])
def test_to_blocked_padding_roundtrip(n, block):
    x = jax.random.normal(jax.random.PRNGKey(n % 997), (n,))
    xb, pad = ops._to_blocked(x, block)
    assert xb.shape[0] % ops.ROWS == 0
    assert xb.shape[1] == block
    assert pad == xb.size - n
    flat = np.asarray(xb.reshape(-1))
    np.testing.assert_array_equal(flat[:n], np.asarray(x, np.float32))
    assert not flat[n:].any(), "pad lanes must be zero"


@fuzz(_st(lambda: st.integers(1, 40_000)),
      fallback=[(1,), (100,), (2048,), (3001,), (5000,), (8 * 2048,)])
def test_pad_lanes_never_billed(n):
    """Kernel payloads are sliced to the logical ceil(n/block) rows, and the
    ledger formulas are identical to the pure twin for arbitrary n — no
    payload bytes are ever attributed to grid-pad lanes."""
    block = 2048
    kern = make_compressor("qsgd:8", backend="kernel")
    pure = make_compressor("qsgd:8")
    x = jax.random.normal(jax.random.PRNGKey(n % 991), (n,))
    pay, _ = kern.encode((), jax.random.PRNGKey(0), x)
    nb_logical = -(-n // block)
    assert pay["q"].shape[0] == nb_logical
    assert pay["scale"].shape == (nb_logical,)
    assert kern.meta_bits(n) == pure.meta_bits(n) == 8.0 * n + 32.0 * nb_logical
    assert kern.wire_bits(n) == pure.wire_bits(n)


def test_stc_ternarize_accepts_traced_fraction():
    """The fused STC op must be static-shape-safe for a *traced* fraction —
    the DGC warm-up anneals it per round (MomentumCorrection._anneal_mask)."""
    n = 5000
    x = jax.random.normal(jax.random.PRNGKey(0), (n,))

    @jax.jit
    def annealed(frac):
        return ops.stc_ternarize(x, frac, block=2048)

    code, mu = annealed(jnp.float32(0.05))
    assert code.shape == (n,)
    k = int(round(n * 0.05))
    assert int((code != 0).sum()) >= k
    # matches the static-fraction call (signs exactly; mu to float tolerance
    # — jit-vs-eager may fuse the tiny mu reduction differently)
    code2, mu2 = ops.stc_ternarize(x, 0.05, block=2048)
    np.testing.assert_array_equal(np.asarray(code), np.asarray(code2))
    np.testing.assert_allclose(float(mu), float(mu2), rtol=1e-6)
    # annealing down transmits fewer coordinates
    code3, _ = annealed(jnp.float32(0.01))
    assert int((code3 != 0).sum()) < int((code != 0).sum())
    # max_fraction bounds the top_k prefix (the DGC schedule's static
    # round-0 fraction) without changing the result: bit-identical codes
    # for every traced fraction at or below the bound
    @jax.jit
    def bounded(frac):
        return ops.stc_ternarize(x, frac, block=2048, max_fraction=0.05)

    for f in (0.05, 0.03, 0.01):
        cb, mb = bounded(jnp.float32(f))
        cu, mu_u = annealed(jnp.float32(f))
        np.testing.assert_array_equal(np.asarray(cb), np.asarray(cu))
        np.testing.assert_allclose(float(mb), float(mu_u), rtol=1e-6)


# ---------------------------------------------------------------------------
# Engine-level backend threading (sim path; the hier edge hop is covered by
# distributed_cases.case_kernel_backend_edge_hop)
# ---------------------------------------------------------------------------

def _sim_run(backend, rounds=2):
    from repro.configs.registry import get_arch
    from repro.core.engine import run_rounds
    from repro.core.simulate import make_sim_step
    from repro.core.types import FLConfig
    from repro.data.synthetic import FedDataConfig, sample_round
    from repro.models.model import Model

    cfg = get_arch("paper_lm")
    model = Model(cfg)
    data = FedDataConfig(vocab_size=cfg.vocab_size, num_clients=4,
                         seq_len=32, batch_per_client=2, heterogeneity=1.5)
    fl = FLConfig(algorithm="fedavg", local_steps=1, local_lr=0.2,
                  uplink_compressor="topk:0.05>>qsgd:8", backend=backend)
    sim = make_sim_step(model, fl, data.num_clients, chunk=32)
    state = sim.init_fn(jax.random.PRNGKey(0))
    state, ms = run_rounds(
        sim.engine, state,
        lambda r: sample_round(data, jax.random.fold_in(
            jax.random.PRNGKey(1), r)), rounds, chunk=rounds)
    return state, ms


def test_engine_backend_threading():
    """FLConfig.backend='kernel' through the sim engine: params and EF
    comm_state match pure JAX within the engine-scope ULP band (the
    pallas_call boundary changes XLA's FMA fusion of surrounding f32 math
    — DESIGN.md §6; supports still match exactly), and the per-round
    ledger bytes bit-match."""
    s_jax, m_jax = _sim_run("jax")
    s_ker, m_ker = _sim_run("kernel")
    for a, b in zip(jax.tree.leaves(s_jax.params),
                    jax.tree.leaves(s_ker.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-8)
    for a, b in zip(jax.tree.leaves(s_jax.comm_state),
                    jax.tree.leaves(s_ker.comm_state)):
        np.testing.assert_array_equal(np.asarray(a) == 0, np.asarray(b) == 0)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-8)
    np.testing.assert_array_equal(np.asarray(m_jax["ledger"].uplink_wire),
                                  np.asarray(m_ker["ledger"].uplink_wire))


def test_ledger_terms_identical_across_backends():
    from repro.configs.registry import get_arch
    from repro.core.engine import ledger_terms
    from repro.core.types import FLConfig
    from repro.models.model import Model
    model = Model(get_arch("paper_lm"))
    for spec in ("stc", "topk:0.01>>qsgd:8", "sketch>>qsgd:8"):
        t_jax, _, _ = ledger_terms(model, FLConfig(uplink_compressor=spec,
                                                   backend="jax"))
        t_ker, _, _ = ledger_terms(model, FLConfig(uplink_compressor=spec,
                                                   backend="kernel"))
        assert t_jax == t_ker, spec


# ---------------------------------------------------------------------------
# Packed wire formats (DESIGN.md §10): pack/unpack round-trips, the
# ledger == payload-bytes invariant, fused-vs-staged equivalence, grammar
# ---------------------------------------------------------------------------

from repro.compress.wire_format import (pack2, pack4, payload_nbytes,
                                        unpack2, unpack4)
from repro.kernels import bitpack

# every spec that can ship packed (the "@fused" surface); qsgd bits > 4 and
# the index/sketch/sign stages have no packed form and must stay staged
PACKABLE_SPECS = ("ternary@fused", "qsgd:4@fused", "qsgd:2@fused",
                  "stc:0.1@fused", "topk:0.05>>qsgd:4@fused",
                  "topk:0.1>>ternary@fused", "stc@fused")


@fuzz(_st(lambda: st.integers(1, 20_000)),
      _st(lambda: st.sampled_from([2, 4])),
      fallback=[(1, 2), (3, 2), (4, 2), (100, 4), (3001, 2), (5000, 4),
                (8 * 2048, 2), (8 * 2048, 4)])
def test_pack_unpack_roundtrip_bitexact(n, bits):
    """pack2/pack4 are lossless on their code range and the tail byte's
    unused fields are zero (pad codes never leak onto the wire)."""
    lo, hi = (-1, 1) if bits == 2 else (-8, 7)
    codes = jax.random.randint(jax.random.PRNGKey(n * 8 + bits), (n,),
                               lo, hi + 1, dtype=jnp.int8)
    pack, unpack, per = (pack2, unpack2, 4) if bits == 2 else \
        (pack4, unpack4, 2)
    packed = pack(codes)
    assert packed.dtype == jnp.uint8 and packed.shape == (-(-n // per),)
    np.testing.assert_array_equal(np.asarray(unpack(packed, n)),
                                  np.asarray(codes))
    if n % per:  # tail fields beyond n must pack to zero bits
        tail = int(np.asarray(packed)[-1]) >> (bits * (n % per))
        assert tail == 0


@fuzz(_st(lambda: st.integers(1, 20_000)),
      _st(lambda: st.sampled_from([2, 4])),
      fallback=[(1, 2), (100, 4), (2048, 2), (3001, 4), (5000, 2),
                (8 * 2048, 4)])
def test_pallas_pack_kernels_match_flat_packing(n, bits):
    """The Pallas pack/unpack kernels, flattened and sliced to the logical
    length, emit BIT-identical bytes to the pure flat packing — the property
    that makes the fused payloads interchangeable across backends."""
    lo, hi = (-1, 1) if bits == 2 else (-8, 7)
    codes = jax.random.randint(jax.random.PRNGKey(n * 4 + bits), (n,),
                               lo, hi + 1, dtype=jnp.int8)
    block = 2048
    cb, _ = ops._to_blocked(codes.astype(jnp.float32), block)
    cb = cb.astype(jnp.int8)
    per = 8 // bits
    packed_k = bitpack.pack_codes_blocked(cb, bits, interpret=True)
    flat_k = packed_k.reshape(-1)[:-(-n // per)]
    flat_p = (pack2 if bits == 2 else pack4)(codes)
    np.testing.assert_array_equal(np.asarray(flat_k), np.asarray(flat_p))
    # kernel unpack inverts kernel pack on the blocked layout
    back = bitpack.unpack_codes_blocked(packed_k, bits, interpret=True)
    np.testing.assert_array_equal(np.asarray(back.reshape(-1)[:n]),
                                  np.asarray(codes))


@fuzz(_st(lambda: st.sampled_from(PACKABLE_SPECS)),
      _st(lambda: st.integers(8, 40_000)),
      fallback=[(s, n) for s in PACKABLE_SPECS for n in (100, 5000)])
def test_packed_payload_bytes_equal_ledger(spec, n):
    """THE tentpole invariant: for every packable spec the bytes the
    aggregation collective actually gathers (payload_nbytes via eval_shape)
    equal the ledger's wire_bits/8 exactly, on both backends — and packing
    strictly shrinks the wire vs the staged twin wherever a byte can hold
    more than one code."""
    staged = make_compressor(spec.replace("@fused", ""))
    for backend in ("jax", "kernel"):
        pipe = make_compressor(spec, backend=backend)
        assert 8 * payload_nbytes(pipe, n) == pipe.wire_bits(n), \
            (spec, backend, n)
        # packing strictly shrinks the wire vs the staged twin — except
        # stc@fused at the default fraction 0.01, where the dense 2-bit
        # sign plane (2n bits) loses to the staged index list (~40*k bits);
        # the dense plane wins exactly when fraction > 2/40 (DESIGN.md §10).
        # A single code (e.g. topk:0.05 keeps k=1 of n=8) pads to one whole
        # byte either way, so there packed == staged and only <= holds.
        if spec != "stc@fused":
            if _staged_codes(staged, n) > 1:
                assert pipe.wire_bits(n) < staged.wire_bits(n), \
                    (spec, backend, n)
            else:
                assert pipe.wire_bits(n) <= staged.wire_bits(n), \
                    (spec, backend, n)


def _staged_codes(staged, n):
    """int8 codes in the staged payload of a length-n leaf: the codes the
    packed twin would share bytes between."""
    state = jax.eval_shape(lambda: staged.init((n,)))
    payload, _ = jax.eval_shape(staged.encode, state,
                                jax.ShapeDtypeStruct((2,), jnp.uint32),
                                jax.ShapeDtypeStruct((n,), jnp.float32))
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(payload)
               if l.dtype == jnp.int8)


def test_fused_stc_matches_staged_pipeline():
    """stc@fused (single threshold-ternarize pass) reconstructs the same
    update as the staged topk>>ternary pipeline: identical support, values
    within mu's reduction-order tolerance, strictly fewer wire bits."""
    n = 5000
    x = jax.random.normal(jax.random.PRNGKey(3), (n,)) * 2.0
    staged = make_compressor("stc:0.1")
    fused = make_compressor("stc:0.1@fused")
    pay_s, _ = staged.encode(staged.init((n,)), jax.random.PRNGKey(0), x)
    pay_f, _ = fused.encode(fused.init((n,)), jax.random.PRNGKey(0), x)
    y_s = np.asarray(staged.decode(pay_s, n))
    y_f = np.asarray(fused.decode(pay_f, n))
    np.testing.assert_array_equal(y_s == 0, y_f == 0)
    np.testing.assert_allclose(y_s, y_f, rtol=1e-5, atol=1e-5)
    assert fused.wire_bits(n) < staged.wire_bits(n)


def test_fused_names_tagged():
    assert make_compressor("ternary@fused").name == "ternary@fused"
    assert make_compressor("stc:0.1@fused").name == "stc0.1@fused"
    assert make_compressor("qsgd:4@fused@kernel").name == "qsgd4@kernel@fused"
    assert make_compressor("topk:0.05>>qsgd:4@fused").name == \
        "topk0.05>>qsgd4@fused"


def test_explicit_fused_on_unpackable_stage_fails():
    for spec in ("topk:0.05@fused", "qsgd:8@fused", "sbc:0.01@fused",
                 "hsq@fused", "sketch@fused"):
        with pytest.raises(ValueError, match="no packed wire format"):
            make_compressor(spec)
    with pytest.raises(ValueError, match="unknown wire format"):
        make_compressor("ternary", wire_format="zipped")


def test_global_wire_format_degrades_gracefully():
    """FLConfig.wire_format='packed' packs every packable stage and leaves
    the rest staged (same graceful-degrade contract as backend='kernel')."""
    assert make_compressor("qsgd:8", wire_format="packed").name == "qsgd8"
    assert make_compressor("qsgd:4", wire_format="packed").name == \
        "qsgd4@fused"
    assert make_compressor("stc", wire_format="packed").name == \
        "stc0.01@fused"
    assert make_compressor("hsq", wire_format="packed").name == "hsq"
    # staged remains the default everywhere
    assert make_compressor("stc").name == "topk0.01>>ternary"


def test_engine_wire_format_packed_halves_uplink():
    """FLConfig.wire_format='packed' through the sim engine: the decoded
    aggregate matches staged within mu tolerance and the ledger's wire
    bytes drop by ~2x (int8 signs -> 2-bit packed, per-leaf +32-bit mu)."""
    from repro.configs.registry import get_arch
    from repro.core.engine import run_rounds
    from repro.core.simulate import make_sim_step
    from repro.core.types import FLConfig
    from repro.data.synthetic import FedDataConfig, sample_round
    from repro.models.model import Model

    cfg = get_arch("paper_lm")
    model = Model(cfg)
    data = FedDataConfig(vocab_size=cfg.vocab_size, num_clients=4,
                         seq_len=32, batch_per_client=2, heterogeneity=1.5)

    def run(wire):
        fl = FLConfig(algorithm="fedavg", local_steps=1, local_lr=0.2,
                      uplink_compressor="stc:0.1", wire_format=wire)
        sim = make_sim_step(model, fl, data.num_clients, chunk=32)
        state = sim.init_fn(jax.random.PRNGKey(0))
        return run_rounds(
            sim.engine, state,
            lambda r: sample_round(data, jax.random.fold_in(
                jax.random.PRNGKey(1), r)), 2, chunk=2)

    s_stg, m_stg = run("staged")
    s_pkd, m_pkd = run("packed")
    for a, b in zip(jax.tree.leaves(s_stg.params),
                    jax.tree.leaves(s_pkd.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    wire_stg = float(np.asarray(m_stg["ledger"].uplink_wire)[-1])
    wire_pkd = float(np.asarray(m_pkd["ledger"].uplink_wire)[-1])
    assert wire_pkd < 0.6 * wire_stg, (wire_pkd, wire_stg)


def test_async_engine_moves_packed_payloads():
    """The async dispatch path ships the packed buffers unchanged: a FedBuff
    run with wire_format='packed' stays finite and its per-event ledger
    reports the packed byte counts."""
    from repro.configs.registry import get_arch
    from repro.core.engine import Topology, make_round_engine, run_rounds
    from repro.core.types import FLConfig
    from repro.data.synthetic import FedDataConfig, sample_round
    from repro.models.model import Model

    cfg = get_arch("paper_lm")
    model = Model(cfg)
    data = FedDataConfig(vocab_size=cfg.vocab_size, num_clients=4,
                         seq_len=32, batch_per_client=2, heterogeneity=1.5)

    def data_fn(r):
        return sample_round(data, jax.random.fold_in(jax.random.PRNGKey(1), r))

    def run(wire):
        fl = FLConfig(algorithm="fedavg", local_steps=1, local_lr=0.2,
                      uplink_compressor="stc:0.1", wire_format=wire)
        eng = make_round_engine(model, fl, Topology.async_(4, buffer_size=2),
                                chunk=32, data_fn=data_fn)
        return run_rounds(eng, eng.init_fn(jax.random.PRNGKey(0)),
                          data_fn, 8, chunk=4)

    _, m_stg = run("staged")
    _, m_pkd = run("packed")
    assert np.isfinite(np.asarray(m_pkd["loss"])).all()
    wire_stg = float(np.asarray(m_stg["ledger"].uplink_wire)[-1])
    wire_pkd = float(np.asarray(m_pkd["ledger"].uplink_wire)[-1])
    assert 0 < wire_pkd < 0.6 * wire_stg, (wire_pkd, wire_stg)
