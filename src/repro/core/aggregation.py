"""Compressed FL aggregation — the wire.

This is where the survey's subject physically happens on the TPU mesh: the
per-client update pytree crosses the ICI/DCN links. The aggregation runs in a
``shard_map`` over the client mesh axes so that **the encoded payload is the
collective operand** — an ``all_gather`` of int8/ternary/top-k arrays, not an
f32 all-reduce. The dry-run's HLO collective-byte count therefore measures
exactly what each pipeline claims to save.

Baseline (Identity) uses a weighted ``psum`` instead (f32 all-reduce — the
FedAvg wire format), so baseline vs compressed is an apples-to-apples HLO
diff.

Pipeline state (error-feedback residuals, DGC momentum, ...): the pipeline
owns it (``CommTransform.init/encode``), the trainer merely threads it. Each
client's state shard lives with its client — a leading C dim over the client
mesh axes — and never crosses the network: ``encode`` consumes and returns it
inside the shard_map body, and only the payload is gathered.
"""
from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.compress.api import CommTransform
from repro.compress.secure_agg import MASK_TAG, has_mask_ctx, inject_mask_ctx

PyTree = Any


def client_axes(mesh: Mesh, client_axis: str) -> tuple:
    if client_axis == "pod":
        return ("pod",) if "pod" in mesh.axis_names else ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def client_index(axes: Sequence[str], mesh: Mesh):
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * dict(mesh.shape)[a] + jax.lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# Pipeline comm-state plumbing (shared by federated.py / simulate.py)
# ---------------------------------------------------------------------------

def comm_state_template(pipe: CommTransform, params: PyTree):
    """Abstract per-leaf pipeline states: a tuple over param leaves of
    ``jax.eval_shape(pipe.init, leaf.shape)`` pytrees."""
    return tuple(jax.eval_shape(functools.partial(pipe.init, tuple(p.shape)))
                 for p in jax.tree.leaves(params))


def comm_state_init(pipe: CommTransform, params: PyTree, lead):
    """Concrete zero state with leading client dim(s) ``lead`` on every array
    (the init contract: pipeline state starts at zero). ``lead`` is the
    global client count C, or a tuple of leading dims — e.g. ``(G, Ce)`` for
    the hierarchical (pod, data) client grid."""
    lead = (lead,) if isinstance(lead, int) else tuple(lead)
    return tuple(
        jax.tree.map(lambda a: jnp.zeros(lead + a.shape, a.dtype), tmpl)
        for tmpl in comm_state_template(pipe, params))


def comm_state_specs(pipe: CommTransform, params: PyTree, param_specs: PyTree,
                     axes: tuple, separate: bool = False):
    """PartitionSpecs for the comm state: client dim(s) over the client axes;
    leaf-shaped state arrays (residuals, momenta) additionally inherit the
    parameter's own sharding, anything else is replicated.

    ``separate=False`` (star/gossip): ONE fused leading dim sharded over all
    ``axes``. ``separate=True`` (hier): one leading dim per axis — e.g.
    ``("pod", "data")`` -> a (G, Ce) client grid."""
    p_leaves = jax.tree.leaves(params)
    s_leaves = jax.tree.leaves(param_specs, is_leaf=lambda s: isinstance(s, P))
    lead = tuple(axes) if separate else ((axes if axes else None),)
    out = []
    for pl, sl in zip(p_leaves, s_leaves):
        tmpl = jax.eval_shape(functools.partial(pipe.init, tuple(pl.shape)))
        out.append(jax.tree.map(
            lambda a, pl=pl, sl=sl: (
                P(*lead, *sl) if tuple(a.shape) == tuple(pl.shape)
                else P(*lead, *([None] * a.ndim))), tmpl))
    return tuple(out)


# ---------------------------------------------------------------------------
# The aggregator
# ---------------------------------------------------------------------------

def make_aggregator(mesh: Mesh, param_specs: PyTree, pipe: CommTransform,
                    client_axis: str = "data", abstract_params: PyTree = None):
    """Returns ``aggregate(deltas, weights, rng, comm_state) ->
    (agg, new_comm_state)`` where deltas have a leading global-client dim
    sharded over the client mesh axes, ``comm_state`` is the pipeline state
    from :func:`comm_state_init` (or None for stateless pipelines), and
    ``agg`` has param shapes.

    ``weights`` (C,) is replicated; zero-weight clients' payloads still cross
    the wire (they were *selected out* — the ledger accounts only selected
    clients' bytes, see federated.py)."""
    axes = client_axes(mesh, client_axis)
    C = int(np.prod([dict(mesh.shape)[a] for a in axes])) if axes else 1

    in_delta_specs = jax.tree.map(lambda s: P(axes if axes else None, *s),
                                  param_specs, is_leaf=lambda s: isinstance(s, P))
    out_agg_specs = param_specs
    stateful = pipe.stateful
    if stateful and abstract_params is None:
        raise ValueError("stateful pipelines need abstract_params to build "
                         "comm-state sharding specs")
    state_specs = (comm_state_specs(pipe, abstract_params, param_specs, axes)
                   if stateful else None)

    def body(deltas, weights, rng, comm_state):
        idx = client_index(axes, mesh) if axes else jnp.zeros((), jnp.int32)
        wsum = jnp.maximum(weights.sum(), 1e-9)
        flat_leaves = jax.tree.leaves(deltas)
        agg_out, st_out = [], []
        for li, leaf in enumerate(flat_leaves):
            local_shape = leaf.shape[1:]          # squeeze local client dim (1)
            flat = leaf.reshape(-1).astype(jnp.float32)
            n = flat.shape[0]
            r = jax.random.fold_in(jax.random.fold_in(rng, li), idx)
            if pipe.is_identity:
                # psum in the delta's own dtype — bf16 deltas (beyond-paper
                # §Perf lever) halve the wire; f32 is the faithful baseline
                contrib = (weights[idx] * flat).astype(leaf.dtype)
                tot = jax.lax.psum(contrib, axes) if axes else contrib
                agg = tot.astype(jnp.float32) / wsum
            else:
                st = (jax.tree.map(lambda a: a[0], comm_state[li])
                      if stateful else pipe.init((n,)))
                if has_mask_ctx(pipe):
                    # secagg context for the star wire: the mask ring spans
                    # the gathered client axis — idx is this device's
                    # client_index, cohort the full C the all_gather sees
                    mkey = jax.random.fold_in(
                        jax.random.fold_in(rng, MASK_TAG), li)
                    st = inject_mask_ctx(st, mkey, idx, C)
                payload, new_st = pipe.encode(st, r, flat)
                if axes:
                    # one fused leading dim of size C, ordered to match
                    # client_index (verified: pod-major, data-minor)
                    gathered = jax.lax.all_gather(payload, axes, tiled=False)
                else:
                    gathered = jax.tree.map(lambda a: a[None], payload)
                dec = jax.vmap(lambda pl_: pipe.decode(pl_, n))(gathered)
                agg = (weights[:, None] * dec).sum(0) / wsum
                if stateful:
                    st_out.append(jax.tree.map(lambda a: a[None], new_st))
            agg_out.append(agg.reshape(local_shape).astype(leaf.dtype))
        agg_tree = jax.tree.unflatten(jax.tree.structure(deltas), agg_out)
        return agg_tree, (tuple(st_out) if stateful else None)

    in_specs = (in_delta_specs, P(), P(), state_specs)
    out_specs = (out_agg_specs, state_specs)

    def aggregate(deltas, weights, rng, comm_state=None):
        # shard_map can't take None pytrees for the state slot when the
        # pipeline is stateless; close over it instead.
        if stateful:
            fn = jax.shard_map(
                lambda d, w, r, s: body(d, w, r, s),
                mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False)
            return fn(deltas, weights, rng, comm_state)
        fn = jax.shard_map(
            lambda d, w, r: body(d, w, r, None)[0],
            mesh=mesh, in_specs=in_specs[:3], out_specs=out_specs[0],
            check_vma=False)
        agg = fn(deltas, weights, rng)
        return agg, None

    return aggregate
