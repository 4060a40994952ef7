"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination against the production mesh, and extract the roofline terms
from the compiled artifact.

  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod1
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b \
      --shape train_4k --mesh pod2 --fl qsgd8

Results land in experiments/dryrun/<mesh>/<fl>/<arch>__<shape>.json and are
the single source for EXPERIMENTS.md §Dry-run and §Roofline.

NOTE: the XLA_FLAGS line below MUST execute before any other jax-importing
module — jax locks the device count at first init.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCH_IDS, get_arch
from repro.configs import shapes as shp
from repro.core.types import FLConfig
from repro.core.federated import make_fl_train_step
from repro.core.hierarchical import make_hier_fl_train_step
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.models import sharding as shd
from repro.models.model import Model, set_activation_mesh

FL_VARIANTS = {
    # paper-faithful baseline: FedAvg/FedSGD with f32 updates on the wire
    "baseline": FLConfig(algorithm="fedsgd", local_steps=1,
                         uplink_compressor="none"),
    # FedPAQ/QSGD quantised uplink + LFL quantised downlink
    "qsgd8": FLConfig(algorithm="fedsgd", local_steps=1,
                      uplink_compressor="qsgd8", downlink_compressor="lfl8"),
    # STC sparse-ternary with error feedback
    "stc": FLConfig(algorithm="fedsgd", local_steps=1,
                    uplink_compressor="stc", topk_fraction=0.01),
    # top-k + error feedback, FedAdam server
    "topk": FLConfig(algorithm="fedsgd", local_steps=1,
                     uplink_compressor="topk", topk_fraction=0.01,
                     server_opt="fedadam", server_lr=0.05),
    # hierarchical (pod2 only; this program is the edge step — the cloud
    # step is a second compiled program). §Perf finding: the edge hop rides
    # ICI where uncompressed psum beats C x int8 gather (see A1), so
    # compression is applied to the cross-pod (DCN) hop only — exactly
    # Hier-Local-QSGD's placement.
    "hier": FLConfig(algorithm="fedavg", local_steps=1, hierarchical=True,
                     uplink_compressor="none", pod_compressor="qsgd8",
                     sync_every=4),
    # combined-scheme pipeline (CommPipeline tentpole): top-k support with
    # QSGD-quantised values — strictly fewer wire bytes than either stage
    # alone; EF residual rides in FLState.comm_state
    "topk_qsgd": FLConfig(algorithm="fedsgd", local_steps=1,
                          uplink_compressor="topk:0.01>>qsgd:8"),
    # DGC: momentum-corrected sparsification (momentum_correction wrapper)
    "dgc": FLConfig(algorithm="fedsgd", local_steps=1,
                    uplink_compressor="topk", topk_fraction=0.01,
                    dgc_momentum=0.9),
    # beyond-paper: uncompressed but bf16 deltas on the wire
    "bf16delta": FLConfig(algorithm="fedsgd", local_steps=1,
                          uplink_compressor="none", delta_dtype="bf16"),
    # beyond-paper combo: quantized wire + bf16 residual path
    "qsgd8_bf16": FLConfig(algorithm="fedsgd", local_steps=1,
                           uplink_compressor="qsgd8",
                           downlink_compressor="lfl8", delta_dtype="bf16"),
}


# ---------------------------------------------------------------------------
# sharding builders for serve-path inputs
# ---------------------------------------------------------------------------

def _dp_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def cache_spec_tree(cache_abs, cfg, mesh, kv_seq_shard=False):
    """kv_seq_shard: shard the cache *sequence* dim over the model axis
    (flash-decode style partial attention; §Perf pair-B optimization) instead
    of splitting heads/head_dim — avoids the resharding XLA otherwise does
    around the attention dots when KV heads don't divide the model axis."""
    sizes = dict(mesh.shape)
    msize = sizes.get("model", 1)
    dp = _dp_axes(mesh)
    dsize = int(np.prod([sizes[a] for a in dp])) if dp else 1

    def leaf_spec(path, leaf):
        key = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
        shape = leaf.shape
        bspec = None
        if key in ("k", "v", "ek", "ev", "kscale", "vscale"):
            nsb, B, L, KV, hd = shape
            if B % dsize == 0 and B >= dsize:
                bspec = dp
                lspec = None
            elif L % dsize == 0 and L >= dsize:
                lspec = dp
            else:
                lspec = None
            if kv_seq_shard and lspec is None and L % msize == 0 \
                    and L >= msize:
                return P(None, bspec, "model", None, None)
            if KV % msize == 0:
                return P(None, bspec, lspec, "model", None)
            if hd % msize == 0:
                return P(None, bspec, lspec, None, "model")
            return P(None, bspec, lspec, None, None)
        if key == "state":
            nsb, B, H, N, Pd = shape
            if B % dsize == 0 and B >= dsize:
                bspec = dp
            return P(None, bspec, "model" if H % msize == 0 else None,
                     None, None)
        if key == "conv":
            nsb, B, W, Cd = shape
            if B % dsize == 0 and B >= dsize:
                bspec = dp
            return P(None, bspec, None, "model" if Cd % msize == 0 else None)
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(leaf_spec, cache_abs)


# ---------------------------------------------------------------------------
# builders: (lowered, n_devices, note) per mode
# ---------------------------------------------------------------------------

CHUNK = 512


def build_train(cfg, shape_cfg, mesh, fl: FLConfig):
    model = Model(cfg)
    if fl.hierarchical:
        step = make_hier_fl_train_step(model, fl, mesh, chunk=CHUNK)
        G, Ce = step.n_pods, step.clients_per_pod
        C = G * Ce
        batch = shp.train_input_specs(cfg, shape_cfg, C)
        # reshape client dim (C,..) -> (G,Ce,..)
        batch = {k: jax.ShapeDtypeStruct((G, Ce) + v.shape[1:], v.dtype)
                 for k, v in batch.items() if k != "resources"}
        bshard = {k: NamedSharding(mesh, P("pod", "data"))
                  for k in batch}
        state_abs = jax.eval_shape(step.init_fn,
                                   jax.ShapeDtypeStruct((2,), jnp.uint32))
        fn = jax.jit(step.step_edge,
                     in_shardings=(step.state_shardings, bshard))
        return fn.lower(state_abs, batch), f"hier edge step C={C}"
    step = make_fl_train_step(model, fl, mesh, chunk=CHUNK)
    batch = shp.train_input_specs(cfg, shape_cfg, step.n_clients)
    state_abs = jax.eval_shape(step.init_fn,
                               jax.ShapeDtypeStruct((2,), jnp.uint32))
    fn = jax.jit(step.step_fn,
                 in_shardings=(step.state_shardings,
                               step.batch_sharding_fn(batch)))
    return fn.lower(state_abs, batch), f"fl train C={step.n_clients}"


def build_prefill(cfg, shape_cfg, mesh):
    model = Model(cfg)
    pspecs = shd.tree_specs(model.abstract_params(), model.logical_axes(),
                            mesh, cfg.fsdp)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    batch = shp.prefill_input_specs(cfg, shape_cfg)
    dp = _dp_axes(mesh)
    dsize = int(np.prod([dict(mesh.shape)[a] for a in dp]))
    B = shape_cfg.global_batch
    bspec = P(dp) if B % dsize == 0 else P()
    bshard = {k: NamedSharding(mesh, bspec) for k in batch}
    fn = jax.jit(lambda p, b: model.prefill(p, b, window=cfg.sliding_window,
                                            chunk=CHUNK),
                 in_shardings=(pshard, bshard))
    return fn.lower(model.abstract_params(), batch), "prefill"


def build_decode(cfg, shape_cfg, mesh, kv_seq_shard=False,
                 kv_int8=False):
    model = Model(cfg)
    pspecs = shd.tree_specs(model.abstract_params(), model.logical_axes(),
                            mesh, cfg.fsdp)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    specs = shp.decode_input_specs(cfg, shape_cfg, quantized=kv_int8)
    cshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          cache_spec_tree(specs["cache"], cfg, mesh,
                                          kv_seq_shard=kv_seq_shard),
                          is_leaf=lambda x: isinstance(x, P))
    dp = _dp_axes(mesh)
    dsize = int(np.prod([dict(mesh.shape)[a] for a in dp]))
    B = shape_cfg.global_batch
    tshard = NamedSharding(mesh, P(dp) if B % dsize == 0 and B >= dsize
                           else P())
    w = shp.decode_window(cfg, shape_cfg)
    fn = jax.jit(lambda p, c, t, pos: model.decode(p, c, t, pos, window=w),
                 in_shardings=(pshard, cshard, tshard,
                               NamedSharding(mesh, P())))
    cache_len = shp.decode_cache_len(cfg, shape_cfg)
    return fn.lower(model.abstract_params(), specs["cache"], specs["token"],
                    specs["pos"]), f"decode cache_len={cache_len} window={w}"


# ---------------------------------------------------------------------------
# model-flops accounting (the "useful compute" numerator)
# ---------------------------------------------------------------------------

def active_params(model: Model) -> tuple:
    """(total, active-per-token) parameter counts (MoE-aware)."""
    import numpy as _np
    cfg = model.cfg
    total, active = 0, 0
    for path, d in jax.tree_util.tree_flatten_with_path(
            model.defs, is_leaf=lambda x: hasattr(x, "logical"))[0]:
        n = int(_np.prod(d.shape))
        total += n
        keys = [str(getattr(p, "key", p)) for p in path]
        if "experts" in d.logical:
            e, k = cfg.num_experts, max(cfg.experts_per_token, 1)
            active += n * k // e
        elif "embed" == keys[-1] or "lm_head" == keys[-1]:
            active += 0        # embeddings are lookups, lm_head counted once
        else:
            active += n
    return total, active


def model_flops(model: Model, shape_cfg) -> float:
    total, active = active_params(model)
    if shape_cfg.mode == "train":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 6.0 * active * tokens
    if shape_cfg.mode == "prefill":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 2.0 * active * tokens
    return 2.0 * active * shape_cfg.global_batch      # decode: 1 token/seq


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_one(arch: str, shape_name: str, mesh_name: str, fl_name: str,
            out_dir: str, force=False, no_remat=False,
            kv_seq_shard=False, kv_int8=False, tag="") -> dict:
    out_path = os.path.join(out_dir, mesh_name, fl_name,
                            f"{arch}__{shape_name}{tag}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    cfg = get_arch(arch)
    if no_remat:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, remat=False)
    shape_cfg = shp.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    set_activation_mesh(mesh)
    n_dev = mesh.size

    rec = {"arch": cfg.name, "shape": shape_name, "mesh": mesh_name,
           "fl": fl_name, "devices": n_dev, "ok": False,
           "no_remat": no_remat, "kv_seq_shard": kv_seq_shard}
    t0 = time.time()
    try:
        if shape_cfg.mode == "train":
            lowered, note = build_train(cfg, shape_cfg, mesh,
                                        FL_VARIANTS[fl_name])
        elif shape_cfg.mode == "prefill":
            lowered, note = build_prefill(cfg, shape_cfg, mesh)
        else:
            lowered, note = build_decode(cfg, shape_cfg, mesh,
                                         kv_seq_shard=kv_seq_shard,
                                         kv_int8=kv_int8)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "peak_gb": getattr(mem, "peak_memory_in_bytes", 0) / 1e9,
        }
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):       # pre-0.5 jax returns [dict]
            ca = ca[0] if ca else {}
        rec["xla_cost"] = {"flops": ca.get("flops", 0.0),
                           "bytes": ca.get("bytes accessed", 0.0)}

        stats = hlo.analyze(compiled.as_text())
        # Memory term: XLA's fusion-aware per-visit bytes, corrected for while
        # trip counts via the flops ratio (XLA cost analysis counts loop
        # bodies once; flops give the exact correction on the same loops).
        # stats.hbm_bytes (instruction-level sum) is kept as an upper bound.
        corr = max(1.0, stats.flops / ca["flops"]) if ca.get("flops") else 1.0
        hbm_est = ca.get("bytes accessed", 0.0) * corr
        stats_est = dataclasses.replace(stats, hbm_bytes=hbm_est) \
            if hbm_est else stats
        # the placeholder CPU devices stand in for the production chips
        terms = hlo.roofline(stats_est, PRODUCTION_DEVICE_KIND)
        model = Model(cfg)
        mf = model_flops(model, shape_cfg) / n_dev
        total, active = active_params(model)
        rec.update({
            "note": note,
            "params_total": total, "params_active": active,
            "hlo_flops_per_dev": stats.flops,
            "hbm_bytes_per_dev": hbm_est or stats.hbm_bytes,
            "hbm_bytes_upper": stats.hbm_bytes,
            "trip_corr": corr,
            "coll_bytes_per_dev": stats.coll_bytes,
            "coll_client_bytes": stats.coll_client_bytes,
            "coll_model_bytes": stats.coll_model_bytes,
            "coll_by_type": stats.coll_by_type,
            "coll_count": stats.coll_count,
            "roofline": terms,
            "dominant": hlo.dominant(terms),
            "model_flops_per_dev": mf,
            "useful_flops_ratio": (mf / stats.flops) if stats.flops else 0.0,
            "ok": True,
        })
    except Exception as e:  # noqa
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    status = "OK " if rec["ok"] else "FAIL"
    print(f"[{status}] {mesh_name}/{fl_name}/{arch}/{shape_name} "
          f"({rec['total_s']}s) "
          + (f"dom={rec.get('dominant')} coll={rec.get('coll_bytes_per_dev', 0)/1e6:.1f}MB"
             if rec["ok"] else rec.get("error", "")), flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2"])
    ap.add_argument("--fl", default="baseline", choices=list(FL_VARIANTS))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--kv-seq-shard", action="store_true")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--fsdp-legacy", action="store_true",
                    help="pre-C1 FSDP placement (contraction-dim data shard)")
    ap.add_argument("--chunk", type=int, default=512,
                    help="attention/xent chunk size (§Perf A5)")
    ap.add_argument("--tag", default="",
                    help="output-filename suffix for §Perf experiments")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    global CHUNK
    CHUNK = args.chunk
    if args.fsdp_legacy:
        shd.FSDP_MODE = "legacy"
    archs = [a for a in ARCH_IDS if a != "paper_lm"] \
        if (args.all or not args.arch) else [args.arch]
    shapes = list(shp.SHAPES) if (args.all or not args.shape) else [args.shape]
    fails = 0
    for a in archs:
        for s in shapes:
            rec = run_one(a, s, args.mesh, args.fl, args.out, args.force,
                          no_remat=args.no_remat,
                          kv_seq_shard=args.kv_seq_shard,
                          kv_int8=args.kv_int8, tag=args.tag)
            fails += 0 if rec["ok"] else 1
    print(f"done; {fails} failures")
    return fails


if __name__ == "__main__":
    raise SystemExit(main())
