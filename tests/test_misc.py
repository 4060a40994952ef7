"""Sharding rules, checkpointing, ledger arithmetic, HLO analyzer."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.types import ArchConfig, CommLedger
from repro.models.sharding import spec_for
from repro.checkpoint import save, restore
from repro.launch.hlo_analysis import (_shape_bytes, _trip_count, analyze,
                                       parse_computations, roofline, dominant)


class FakeMesh:
    def __init__(self, **shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_spec_for_basic_tp():
    mesh = FakeMesh(data=16, model=16)
    # ffn weight: model on the hidden dim
    assert spec_for((512, 4096), ("embed", "ffn"), mesh, False) == \
        P(None, "model")
    # experts preferred over ffn
    assert spec_for((32, 512, 4096), ("experts", "embed", "ffn"),
                    mesh, False) == P("model", None, None)
    # fsdp 'extend' mode: widen the model dim when divisible by model*data...
    assert spec_for((512, 4096), ("embed", "ffn"), mesh, True) == \
        P(None, ("model", "data"))
    # ...else shard the rightmost eligible (output) dim — never contraction
    s = spec_for((32, 512, 4096), ("experts", "embed", "ffn"), mesh, True)
    assert s == P("model", None, "data")
    # non-divisible stays unsharded (50280 vocab)
    assert spec_for((50280, 1024), ("vocab", "embed"), mesh, False) == \
        P(None, "model")
    # norms never shard
    assert spec_for((1024,), ("norm",), mesh, False) == P(None)


def test_spec_never_reuses_axis():
    mesh = FakeMesh(data=4, model=4)
    s = spec_for((16, 16), ("ffn", "vocab"), mesh, True)
    flat = [a for a in s if a is not None]
    assert len(flat) == len(set(flat))


def test_checkpoint_roundtrip():
    tree = {"a": jnp.arange(6.0).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.npz")
        save(path, tree)
        got = restore(path, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree))
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_roundtrip_population_store():
    """The ClientPopulation residual-store comm_state (slab + id map +
    sketch tail) is a plain dict pytree and must survive save/restore
    bit-for-bit — resuming a 1M-client run needs the slab contents AND
    the id->slot mapping intact (DESIGN.md §9)."""
    from repro.core.engine import uplink_pipeline
    from repro.core.population import ClientPopulation
    from repro.core.types import FLConfig

    pop = ClientPopulation(n_clients=1000, cohort=4, capacity=8,
                           eviction="sketch", tail_cols=256)
    pipe = uplink_pipeline(FLConfig(uplink_compressor="topk:0.25>>qsgd:8"))
    params = {"w": jnp.zeros((12,), jnp.float32)}
    store = pop.make_store(pipe, params)
    s = store.init()
    for r in range(3):          # populate slab, stamps, and the tail
        ids = pop.cohort_ids(r)
        rows, s = store.gather(s, ids)
        rows = jax.tree.map(lambda a: a + jnp.float32(r + 1), rows)
        s = store.scatter(s, ids, rows)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "store.npz")
        save(path, s)
        got = restore(path, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), s))
        for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_selection_top_m_mask_exact_on_ties():
    """Regression (rank-based tie-break): ``scores >= thresh`` over-selected
    whole tie groups at the cut — the mask must have exactly m ones, with
    ties broken deterministically by ascending index."""
    import jax.numpy as jnp
    from repro.core import selection as sel
    from repro.core.types import FLConfig

    # all-equal scores: the old thresholding selected all C
    m = sel._top_m_mask(jnp.ones((10,)), 3)
    assert float(m.sum()) == 3.0
    assert np.asarray(m)[:3].all()            # lowest indices win ties
    # partial tie at the threshold
    m = sel._top_m_mask(jnp.array([1.0, 2.0, 2.0, 2.0, 0.5]), 2)
    np.testing.assert_array_equal(np.asarray(m), [0, 1, 1, 0, 0])
    # end-to-end: random selection draws can tie only pathologically, but
    # multi_criteria scores (resource means) tie easily — exactly m selected
    fl = FLConfig(selection="multi_criteria", clients_per_round=2)
    w = sel.select(fl, jax.random.PRNGKey(0),
                   losses=jnp.zeros((6,)),
                   resources=jnp.full((6, 4), 0.5),
                   sizes=jnp.ones((6,)))
    assert float((w > 0).sum()) == 2.0


def test_ledger_arithmetic():
    z = CommLedger.zero()
    l1 = CommLedger(*(jnp.float32(x) for x in (10, 8, 4, 100, 100)))
    tot = z + l1 + l1
    assert float(tot.uplink_wire) == 20
    assert float(l1.compression_ratio()) == pytest.approx(200 / 14.0)


HLO = """
HloModule test

%cond (p: (s32[], f32[4])) -> pred[] {
  %p = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %x = f32[4] get-tuple-element(%p), index=1
  %ar = f32[4] all-reduce(%x), replica_groups=[2,4]<=[8], to_apply=%add
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[4]) tuple(%i, %ar)
}

ENTRY %main (a: f32[4]) -> (s32[], f32[4]) {
  %a = f32[4] parameter(0)
  %c0 = s32[] constant(0)
  %t0 = (s32[], f32[4]) tuple(%c0, %a)
  ROOT %w = (s32[], f32[4]) while(%t0), condition=%cond, body=%body
}
"""


def test_hlo_analyzer_trip_counts():
    st = analyze(HLO)
    # all-reduce of f32[4] = 16B, wire 2x, 7 trips
    assert st.coll_bytes == pytest.approx(2 * 16 * 7)
    assert st.coll_count == 7
    assert "all-reduce" in st.coll_by_type


def test_shape_bytes():
    assert _shape_bytes("bf16[2,3]") == 12
    assert _shape_bytes("(f32[4], s8[8])") == 24
    assert _shape_bytes("pred[]") == 1


def test_roofline_dominant():
    from repro.launch.hlo_analysis import HLOStats
    st = HLOStats(flops=197e12, hbm_bytes=819e9 * 3, coll_bytes=50e9 * 2)
    terms = roofline(st, "TPU v5 lite")
    assert terms["compute_s"] == pytest.approx(1.0)
    assert dominant(terms) == "memory"
    with pytest.raises(ValueError, match="no published peaks"):
        roofline(st, "cpu")


def test_reduced_configs_are_small():
    from repro.configs.registry import ARCH_IDS, get_smoke
    for a in ARCH_IDS:
        cfg = get_smoke(a)
        from repro.models.model import Model
        assert Model(cfg).param_count() < 30e6, a


def test_group_stride_classification():
    from repro.launch.hlo_analysis import _group_stride
    # explicit list, stride 16 => client axis
    assert _group_stride("replica_groups={{0,16,32,48},{1,17,33,49}}") == 16
    # contiguous iota => model axis
    assert _group_stride("replica_groups=[16,16]<=[256]") == 1
    # strided iota (data axis of a (16,16) mesh)
    assert _group_stride("replica_groups=[16,16]<=[16,16]T(1,0)") == 16
    # model-subgroup with inner transpose (from the qwen attention HLO)
    assert _group_stride("replica_groups=[32,8]<=[16,8,2]T(0,2,1)") == 2


def test_fl_variants_cover_paper_and_beyond():
    from repro.launch.dryrun import FL_VARIANTS
    assert {"baseline", "qsgd8", "stc", "topk", "hier"} <= set(FL_VARIANTS)
    assert FL_VARIANTS["baseline"].uplink_compressor == "none"
    assert FL_VARIANTS["hier"].hierarchical
    # §Perf: hier compresses the DCN hop only
    assert FL_VARIANTS["hier"].uplink_compressor == "none"
    assert FL_VARIANTS["hier"].pod_compressor != "none"
