"""Shared differential-parity case table for the kernel wire backend.

One table drives the whole harness (tests/test_kernel_parity.py): every
kernel-capable stage, the combined-sweep chains, and the stateful EF/DGC
wrappers, each run through BOTH backends on identical inputs. The same
table validates unchanged on real TPU — the kernels pick interpret mode vs
Mosaic from ``jax.default_backend()`` (``repro.kernels.ops._interpret``).

Parity classes (DESIGN.md §6):

  * ``exact=True``  — the kernel is deterministic and the blocked layout
    does not reorder any reduction: decoded payloads, comm_state, and
    ledger bytes must match BIT-EXACTLY (qsgd: shared uniforms sampled in
    the pure blocked layout; topk: the ``lax.top_k`` tie order, ascending
    index, preserved through the masking pass).
  * ``exact=False`` — padding/blocking reorders a reduction (ternary's mu
    partial sums, count-sketch's per-chunk matmul accumulation): decoded
    payloads and state match within ``tol`` (relative, against the input
    scale), signs/supports still exactly.

``sizes`` sweeps n across the kernel layout boundaries: below one block,
non-multiples of block and of block*ROWS, and an exact grid multiple.
"""
import jax
import jax.numpy as jnp


# n values vs the kernel blocking (block=2048 unless a case overrides it,
# grid rows padded to multiples of ROWS=8): sub-block, ragged, exact grid.
SIZES = (100, 3001, 5000, 8 * 2048)


def gaussian(seed, n):
    return jax.random.normal(jax.random.PRNGKey(seed), (n,)) * 2.0


def heavy_hitters(seed, n):
    """Planted heavy hitters over small noise — sketch decode recovers a
    stable top-k support, so near-tie selection flips cannot mask a real
    parity break."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = 0.01 * jax.random.normal(k1, (n,))
    m = max(4, n // 100)
    idx = jax.random.choice(k2, n, (m,), replace=False)
    spikes = jnp.where(jnp.arange(m) % 2 == 0, 1.0, -1.0) * \
        (5.0 + jnp.arange(m, dtype=jnp.float32))
    return x.at[idx].set(spikes)


def ties(seed, n):
    """Gaussian values rounded to a few levels: whole groups of entries
    share the k-th magnitude, so the top-k cut falls inside a tie."""
    return jnp.round(gaussian(seed, n) * 2.0) / 2.0


INPUTS = {"gaussian": gaussian, "hh": heavy_hitters, "ties": ties}


def case(name, spec, *, exact=True, tol=0.0, input="gaussian",
         wrapper=None, rounds=1, kw=None, sizes=SIZES, base=None):
    return dict(name=name, spec=spec, exact=exact, tol=tol, input=input,
                wrapper=wrapper, rounds=rounds, kw=kw or {}, sizes=sizes,
                base=base)


# --- every kernel-capable stage, standalone --------------------------------
STAGE_CASES = [
    case("topk", "topk:0.05"),
    case("topk_ties", "topk:0.05", input="ties"),
    case("qsgd8", "qsgd:8"),
    case("qsgd4", "qsgd:4"),
    case("qsgd_block256", "qsgd:8,256"),
    case("ternary", "ternary", exact=False, tol=1e-5),
    case("stc", "stc:0.05", exact=False, tol=1e-5),
    case("sketch", "sketch:3,512", exact=False, tol=1e-3, input="hh"),
]

# --- chained specs from the combined-scheme sweep --------------------------
CHAIN_CASES = [
    case("topk_qsgd8", "topk:0.01>>qsgd:8"),
    case("topk_qsgd4", "topk:0.05>>qsgd:4"),
    case("topk_ternary", "topk:0.1>>ternary", exact=False, tol=1e-5),
    case("sketch_qsgd8", "sketch:3,512>>qsgd:8", exact=False, tol=1e-3,
         input="hh"),
]

# --- EF / DGC momentum wrappers (comm_state evolution across rounds) -------
WRAPPER_CASES = [
    case("ef_topk_qsgd", "topk:0.05>>qsgd:8", wrapper="ef", rounds=3),
    case("ef_stc", "stc:0.05", wrapper="ef", exact=False, tol=1e-5,
         rounds=3),
    case("mc_topk", "topk", wrapper="mc", rounds=3,
         kw=dict(fraction=0.05)),
    case("mc_warmup_topk", "topk", wrapper="mc_warmup", rounds=4,
         kw=dict(fraction=0.02), sizes=(3001, 5000)),
]

# --- packed wire formats ("@fused", DESIGN.md §10) -------------------------
# The packed payload bytes must be BIT-equal across backends (the fused
# pack kernels emit exactly wire_format.pack2/pack4 of the staged codes) —
# test_backend_parity's integer-dtype comparison enforces that on the
# payloads via comm_state/decode; the dedicated round-trip tests in
# test_kernel_parity.py cover the raw byte streams. mu partial sums keep
# the bounded-ULP class of their staged twins.
FUSED_CASES = [
    case("ternary_fused", "ternary@fused", exact=False, tol=1e-5),
    case("qsgd4_fused", "qsgd:4@fused"),
    case("qsgd2_fused", "qsgd:2@fused"),
    case("stc_fused", "stc:0.1@fused", exact=False, tol=1e-5),
    case("topk_qsgd4_fused", "topk:0.05>>qsgd:4@fused"),
    case("topk_ternary_fused", "topk:0.1>>ternary@fused", exact=False,
         tol=1e-5),
    case("ef_stc_fused", "stc:0.1@fused", wrapper="ef", exact=False,
         tol=1e-5, rounds=3),
]

# --- privacy stages (secagg masking / dpnoise, DESIGN.md §11) --------------
# Each privacy case pairs a masked spec with its clear ``base`` spec: the
# kernel-parity run exercises the masked pipeline through both backends
# (ALL_CASES membership), and tests/test_secure_agg.py additionally runs
# the masked-vs-base differential (bit-exact decode after mask removal,
# identical ledger wire bytes).  dpnoise:0 with an inf clip is the proven
# bit-exact no-op, so its masked-vs-base differential is also exact.
PRIVACY_CASES = [
    case("secagg_qsgd4", "qsgd:4>>secagg", base="qsgd:4"),
    case("secagg_topk_qsgd", "topk:0.05>>qsgd:4>>secagg",
         base="topk:0.05>>qsgd:4"),
    case("secagg_ternary_fused", "ternary@fused>>secagg",
         base="ternary@fused", exact=False, tol=1e-5),
    case("secagg_ef_chain", "topk:0.05>>qsgd:8>>secagg",
         base="topk:0.05>>qsgd:8", wrapper="ef", rounds=3),
    case("secagg_qsgd2_fused", "qsgd:2@fused>>secagg", base="qsgd:2@fused"),
]

ALL_CASES = (STAGE_CASES + CHAIN_CASES + WRAPPER_CASES + FUSED_CASES
             + PRIVACY_CASES)


def build(c, backend):
    """Materialise one case's pipeline for a backend."""
    from repro.compress import make_compressor
    from repro.compress.pipeline import error_feedback, momentum_correction
    if c["wrapper"] == "mc_warmup":
        # warm-up widens the wire capacity; the annealed mask shrinks the
        # effective support inside it (pipeline.MomentumCorrection)
        target = c["kw"].get("fraction", 0.02)
        warmup = 2
        wide = target ** (1.0 / (warmup + 1.0))
        return momentum_correction(
            make_compressor(c["spec"], backend=backend, fraction=wide),
            momentum=0.9, warmup_rounds=warmup, final_fraction=target)
    pipe = make_compressor(c["spec"], backend=backend, **c["kw"])
    if c["wrapper"] == "ef":
        pipe = error_feedback(pipe)
    elif c["wrapper"] == "mc":
        pipe = momentum_correction(pipe, momentum=0.9)
    return pipe


# --- scenario conformance cases (core.scenario, DESIGN.md §13) -------------
# Each entry is a *degenerate-but-enabled* scenario: the dynamics hops ARE
# in the graph (Scenario.enabled is True, so this is not the trivial
# statically-skipped path) but every mask they draw is the identity — the
# square/diurnal traces at duty 1.0 emit all-ones, the epoch-scale floor
# 1.0 clips every client to the full local_steps budget.  The conformance
# harness (tests/test_scenario.py) asserts params, comm_state, and ledger
# bytes stay BIT-EXACT vs the scenario-free engines across these wire
# specs — including the Pallas kernel path, the bit-packed fused wire, and
# the secagg masked wire.
def scenario_case(name, spec, **fl_kw):
    return dict(name=name, spec=spec, fl=fl_kw)


SCENARIO_CASES = [
    scenario_case("square_duty1_ef", "topk:0.25>>qsgd:8",
                  scenario_trace="square"),
    scenario_case("diurnal_rate1_kernel", "topk:0.25@kernel>>qsgd:8",
                  scenario_trace="diurnal"),
    scenario_case("escale_floor1_fused", "qsgd:4@fused",
                  scenario_epoch_scale=1.0),
    scenario_case("square_duty1_secagg", "qsgd:4>>secagg",
                  scenario_trace="square"),
    scenario_case("diurnal_escale_combo", "topk:0.25>>qsgd:8",
                  scenario_trace="diurnal", scenario_epoch_scale=1.0),
]
