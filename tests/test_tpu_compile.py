"""Mosaic compile guard: every Pallas kernel compiles for a TPU v5e.

Interpret mode (the CPU test path) accepts kernels that Mosaic refuses —
rank-1 per-row blocks, strided lane slices, int8 vector arithmetic — so
these tests compile each kernel with ``interpret=False`` for a *described*
v5e (no chip needed: the TPU compiler is installed) and assert the Mosaic
custom call survives in the compiled program.  Two sizes per kernel:

  * small — the 1536-element leaves of ``mamba2_370m`` (``A_log``, ``D``,
    ``dt_bias`` stacked over 48 layers), vmapped over 2 clients the way the
    engine's sim wire calls the kernels, with a sketch width that is not a
    power of two; the QSGD kernels get the 10-value top-1% carrier of the
    1024-element ``final_ln`` leaf, so their block adapts to 10 lanes;
  * full — the largest full-width ``mamba2_370m`` leaf, one client.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compress.sketch import CountSketch
from repro.kernels import bitpack, count_sketch, qsgd, ternary, topk_mask
from repro.kernels.ops import _to_blocked

SMALL_N = 48 * 32
SMALL_CARRIER = 10          # round(0.01 * 1024): topk:0.01 of final_ln
BLOCK = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip lands in the persistent cache but can
    # never be read back without one: keep the cache off around these
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def full_n():
    from repro.configs.registry import get_arch
    from repro.models.model import Model
    params = Model(get_arch("mamba2_370m")).abstract_params()
    return max(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def _blocked_shape(n, block=BLOCK):
    return jax.eval_shape(lambda x: _to_blocked(x, block)[0],
                          jax.ShapeDtypeStruct((n,), jnp.float32)).shape


def _case(kernel, n):
    """(fn, arg shapes/dtypes) for one kernel call on a length-n leaf."""
    f32 = jnp.float32
    if kernel == "qsgd_quantize":
        s = _blocked_shape(n, min(BLOCK, n))
        return (lambda x, u: qsgd.qsgd_quantize_blocked(x, u, bits=8),
                [(s, f32), (s, f32)])
    if kernel == "qsgd_pack":
        s = _blocked_shape(n, min(BLOCK, n))
        return (lambda x, u: bitpack.qsgd_pack_blocked(x, u, bits=4),
                [(s, f32), (s, f32)])
    if kernel == "ternarize":
        return (ternary.ternarize_blocked,
                [(_blocked_shape(n), f32), ((), f32)])
    if kernel == "ternarize_pack":
        return (bitpack.ternarize_pack_blocked,
                [(_blocked_shape(n), f32), ((), f32)])
    if kernel == "threshold_sparsify":
        return (topk_mask.threshold_sparsify_blocked,
                [(_blocked_shape(n), f32), ((), f32)])
    if kernel == "count_at_least":
        return (lambda xr, c: topk_mask.count_at_least_rows(xr, c, n=n),
                [((-(-n // topk_mask.LANES), topk_mask.LANES), f32),
                 ((1, topk_mask.CANDIDATES), jnp.int32)])
    if kernel == "pack_codes":
        return (lambda c: bitpack.pack_codes_blocked(c, bits=2),
                [(_blocked_shape(n), jnp.int8)])
    if kernel == "unpack_codes":
        nb, block = _blocked_shape(n)
        return (lambda p: bitpack.unpack_codes_blocked(p, bits=4),
                [((nb, block // 2), jnp.uint8)])
    if kernel == "count_sketch":
        rows = 5
        cols = CountSketch(rows=rows)._cols(n)
        padded = -(-n // count_sketch.CHUNK) * count_sketch.CHUNK
        return (lambda x, a, b: count_sketch.count_sketch(x, a, b, rows,
                                                          cols),
                [((padded,), f32), ((rows,), jnp.uint32),
                 ((rows,), jnp.uint32)])
    raise ValueError(kernel)


KERNELS = ("qsgd_quantize", "qsgd_pack", "ternarize", "ternarize_pack",
           "threshold_sparsify", "count_at_least", "pack_codes",
           "unpack_codes", "count_sketch")


@pytest.mark.parametrize("size", ["small", "full"])
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(kernel, size, one_chip, full_n):
    n, clients = (SMALL_N, 2) if size == "small" else (full_n, 1)
    if size == "small" and kernel.startswith("qsgd"):
        n = SMALL_CARRIER
    fn, specs = _case(kernel, n)
    if clients > 1:
        fn = jax.vmap(fn)
        specs = [((clients,) + s, d) for s, d in specs]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (kernel, size)


SELECT_N = 48 * 1024         # the ln leaf of mamba2_370m, above SORT_BELOW


@pytest.mark.parametrize("backend", ["jax", "kernel"])
def test_topk_encode_sorts_only_the_survivors(backend, one_chip, monkeypatch):
    """``TopK.encode`` compiled for a v5e, vmapped over 2 clients: the only
    sort left is over the k survivors, none over the n-long leaf, and the
    selection's temporaries stay within two n-long 4-byte buffers per
    client (``lax.top_k`` held two) plus a small fixed slack."""
    import re
    from repro.compress.sparsification import TopK, _k
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n, clients, slack = SELECT_N, 2, 1 << 20
    topk = TopK(0.01, backend=backend)
    fn = jax.vmap(lambda x: topk.encode((), None, x)[0])
    x = jax.ShapeDtypeStruct((clients, n), jnp.float32, sharding=one_chip)
    compiled = jax.jit(fn).lower(x).compile()
    sorts = [line for line in compiled.as_text().splitlines()
             if re.search(r"\bsort\(", line)]
    dims = {int(d) for line in sorts
            for d in re.findall(r"\[(?:\d+,)*?(\d+)\]", line.split("sort(")[0])}
    assert sorts and _k(n, 0.01) in dims and n not in dims, sorts
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 2 * clients * n * 4 + slack, temp
