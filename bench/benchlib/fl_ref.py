"""Plain reference of a synchronous FedAvg round with a compressed uplink.

Each client takes ``local_steps`` steps of SGD at ``local_lr`` from the
global weights on its own batch; its update (final minus initial weights)
goes through the uplink pipeline, with error feedback (the residual of what
the pipeline lost is added to the next round's update) when the pipeline is
biased and the traffic asks for it; the server adds the size-weighted mean
of the decoded updates to its weights.  Weights are stored in the type the
configuration states (``store``) and computed on in float32 at the highest
matmul precision, or in ``compute`` where that is given: bfloat16 matmul
operands and activations, as the program computes, which reads how far
the program's precision alone moves the numbers compared.

This module imports nothing of the program and takes nothing from it: the
weights come from ``benchlib.weights`` and the batches from
``benchlib.traffic``, both from the seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import spec as specs

# The fault ``Reference.rounds`` can plant, for reading upper limits:
#   half - the second half of the cohort left out, mean over the rest


def parse(spec: str):
    """``"topk:0.01>>qsgd:8@kernel"`` -> [(name, arg, fused), ...]."""
    out = []
    for tok in spec.split(">>"):
        head, *suffix = tok.split("@")
        name, _, arg = head.partition(":")
        out.append((name, arg or None, "fused" in suffix))
    return out


def stages(spec: str):
    return [(specs.required_module("wire", name), arg, fused)
            for name, arg, fused in parse(spec)]


def payload_bytes(spec: str, n: int, opts: dict) -> int:
    """Bytes of one client's payload for a leaf of n values."""
    total = 0
    for mod, arg, fused in stages(spec):
        b, carrier = mod.payload(n, arg, fused, opts)
        total += b
        n = carrier or n
    return total


def uses_error_feedback(spec: str, opts: dict) -> bool:
    return bool(opts.get("error_feedback", True)) and any(
        mod.biased for mod, _, _ in stages(spec))


def roundtrip_fn(spec: str, opts: dict):
    """``f(x, key) -> decoded x`` for a flat float32 vector."""
    chain = stages(spec)

    def run(i, x, key):
        if i == len(chain):
            return x
        mod, arg, _ = chain[i]
        return mod.roundtrip(x, jax.random.fold_in(key, i), arg,
                             lambda c, k: run(i + 1, c, k), opts)

    return lambda x, key: run(0, x, key)


class Reference:
    """``rounds(params0, data_fn, n)`` follows the first n rounds and
    returns per-round losses and per-leaf norms."""

    def __init__(self, family, sizes: dict, traffic: dict, store,
                 compute=jnp.float32):
        self.family, self.sizes, self.store = family, sizes, store
        fl = traffic["fl"]
        self.steps, self.lr = int(fl["local_steps"]), float(fl["local_lr"])
        self.ef = uses_error_feedback(fl["uplink_compressor"], fl)
        rt = roundtrip_fn(fl["uplink_compressor"], fl)
        self.clients = traffic["clients"]
        store_fn = self._store

        def local(params, batch):
            loss_grad = jax.value_and_grad(lambda p: family.loss(
                {k: v.astype(compute) for k, v in p.items()}, batch, sizes))
            p32 = {k: v.astype(jnp.float32) for k, v in params.items()}
            loss0, g0 = loss_grad(p32)
            gnorm = {k: jnp.linalg.norm(v) for k, v in g0.items()}
            if self.steps == 1:
                return loss0, {k: -self.lr * v for k, v in g0.items()}, gnorm

            def step(carry, _):
                p, g = carry
                p = {k: store_fn(p[k] - self.lr * g[k]) for k in p}
                loss, g = loss_grad(p)
                return (p, g), loss

            (p, g), losses = jax.lax.scan(step, (p32, g0), None,
                                          length=self.steps - 1)
            p = {k: store_fn(p[k] - self.lr * g[k]) for k in p}
            mean_loss = (loss0 + losses.sum()) / self.steps
            return mean_loss, {k: p[k] - p32[k] for k in p}, gnorm

        ef = self.ef

        def client(params, batch, c, resid, agg, w, key):
            bc = {k: (v[c].astype(compute)
                      if jnp.issubdtype(v.dtype, jnp.floating) else v[c])
                  for k, v in batch.items()}
            loss, delta, gnorm = local(params, bc)
            new_resid = {}
            for li, k in enumerate(sorted(delta)):
                y = delta[k].reshape(-1)
                if ef:
                    y = y + resid[k].reshape(-1)
                dec = rt(y, jax.random.fold_in(key, li))
                if ef:
                    new_resid[k] = (y - dec).reshape(delta[k].shape)
                agg[k] = agg[k] + w * dec.reshape(delta[k].shape)
            return loss, gnorm, (new_resid if ef else resid), agg

        def server(params, agg, wsum):
            return {k: store_fn(params[k].astype(jnp.float32) + agg[k] / wsum)
                    .astype(store) for k in params}

        self._client = jax.jit(client, donate_argnums=(3, 4))
        self._server = jax.jit(server, donate_argnums=(1,))
        self._zeros = jax.jit(lambda p: {k: jnp.zeros(v.shape, jnp.float32)
                                         for k, v in p.items()})
        self._norms = jax.jit(lambda a, b: {
            k: jnp.linalg.norm(a[k].astype(jnp.float32)
                               - b[k].astype(jnp.float32)) for k in a})

    def _store(self, x):
        return x.astype(self.store).astype(jnp.float32)

    def rounds(self, params0: dict, data_fn, n: int, key, fault=None):
        with jax.default_matmul_precision("highest"):
            return self._rounds(params0, data_fn, n, key, fault)

    def _rounds(self, params0, data_fn, n, key, fault):
        C = self.clients
        p0 = jax.jit(lambda p: {k: v.astype(self.store)
                                for k, v in p.items()})(params0)
        params = p0
        resid = [self._zeros(p0) if self.ef else None for _ in range(C)]
        losses, out = [], {}
        for r in range(n):
            batch = data_fn(r)
            w = np.asarray(batch.pop("sizes"), np.float64)
            if fault == "half":
                w[C - C // 2:] = 0.0
            agg = self._zeros(p0)
            loss_sum, gnorms = 0.0, {}
            for c in range(C):
                kc = jax.random.fold_in(jax.random.fold_in(key, r), c)
                loss, gn, resid[c], agg = self._client(
                    params, batch, c, resid[c], agg, np.float32(w[c]), kc)
                loss_sum += w[c] * float(loss)
                for k, v in gn.items():
                    gnorms[k] = gnorms.get(k, 0.0) + w[c] * float(v)
            wsum = float(w.sum())
            params = self._server(params, agg, np.float32(wsum))
            losses.append(loss_sum / wsum)
            if r == 0:
                out["grad_norms"] = {k: v / wsum for k, v in gnorms.items()}
                out["norms1"] = {k: float(v) for k, v in
                                 self._norms(params, p0).items()}
        out["norms3"] = {k: float(v) for k, v in
                         self._norms(params, p0).items()}
        out["losses"] = losses
        return out
