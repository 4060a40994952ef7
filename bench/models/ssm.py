"""Plain reference for the ``ssm`` family (Mamba-2 stacks), float32.

Written from the Mamba-2 paper (arXiv:2405.21060) and the published
state-spaces/mamba2-370m sizes, and kept apart from the program: it imports
nothing of ``repro``.  It follows the program's layer equations where they
depart from the published block, so that a sound program agrees with it:

* RMSNorm (no bias) in front of each block and at the end;
* the gate is applied after the inner norm, ``rmsnorm(y) * silu(z)``
  (the published block gates before the norm).

The output head is the embedding's transpose where ``tie_embeddings`` is
set, as published, and a leaf of its own otherwise.

The SSD mixer is computed in its quadratic (attention-like) form over the
whole sequence: ``y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s
x_s + D x_t``.  The program computes the same sum chunk by chunk; nothing of
its chunking is shared here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dims(s):
    d_inner = s["ssm_expand"] * s["d_model"]
    heads = d_inner // s["ssm_head_dim"]
    n = s["ssm_state"]
    conv_dim = d_inner + 2 * n
    return d_inner, heads, n, conv_dim, 2 * d_inner + 2 * n + heads


def layout(s):
    """``{path: (shape, init)}`` of every parameter leaf."""
    D, V, L = s["d_model"], s["vocab_size"], s["num_layers"]
    d_inner, H, N, conv_dim, d_in_proj = dims(s)
    m = "layers/b0/mixer/"
    out = {
        "embed": ((V, D), "normal"),
        "final_ln": ((D,), "ones"),
        m + "ln": ((L, D), "ones"),
        m + "in_proj": ((L, D, d_in_proj), "normal"),
        m + "conv_w": ((L, s["ssm_conv_width"], conv_dim), "normal"),
        m + "conv_b": ((L, conv_dim), "zeros"),
        m + "A_log": ((L, H), "alog"),
        m + "D": ((L, H), "ones"),
        m + "dt_bias": ((L, H), "zeros"),
        m + "norm": ((L, d_inner), "ones"),
        m + "out_proj": ((L, d_inner, D), "normal"),
    }
    if not s["tie_embeddings"]:
        out["lm_head"] = ((D, V), "normal")
    return out


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _block(p, x, s):
    eps = s["norm_eps"]
    d_inner, H, N, conv_dim, _ = dims(s)
    P, W = s["ssm_head_dim"], s["ssm_conv_width"]
    Bt, S, _ = x.shape
    zxbcdt = _rmsnorm(x, p["ln"], eps) @ p["in_proj"]
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
    dt = zxbcdt[..., d_inner + conv_dim:]
    # depthwise causal convolution of width W
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + S] * p["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :d_inner].reshape(Bt, S, H, P)
    Bm = xbc[..., d_inner:d_inner + N]
    Cm = xbc[..., d_inner + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                     # (B,S,H)
    a = dt * -jnp.exp(p["A_log"])                               # (B,S,H)
    cum = jnp.cumsum(a, axis=1)
    seg = cum[:, :, None, :] - cum[:, None, :, :]               # (B,t,s,H)
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", Cm, Bm)
    w = cb[..., None] * decay * dt[:, None, :, :]               # (B,t,s,H)
    y = jnp.einsum("btsh,bshp->bthp", w, xs) + p["D"][:, None] * xs
    y = _rmsnorm(y.reshape(Bt, S, d_inner), p["norm"], eps) * jax.nn.silu(z)
    return x + y @ p["out_proj"]


def hidden(params, batch, s):
    x = params["embed"][batch["tokens"]]
    m = "layers/b0/mixer/"
    stack = {k[len(m):]: v for k, v in params.items() if k.startswith(m)}

    @jax.checkpoint
    def body(x, p):
        return _block(p, x, s), None

    x, _ = jax.lax.scan(body, x, stack)
    return _rmsnorm(x, params["final_ln"], s["norm_eps"])


def loss(params, batch, s):
    """Mean next-token cross-entropy over ``batch["mask"]``."""
    x = hidden(params, batch, s)
    head = params["embed"].T if s["tie_embeddings"] else params["lm_head"]
    logits = (x @ head).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)
    nll = jax.nn.logsumexp(logits, -1) - gold[..., 0]
    mask = batch["mask"]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def extra_inputs(key, lead, s):
    """Inputs besides tokens that one sequence needs: none."""
    return {}


def step_flops(s, seq_len):
    """FLOPs of one sequence's forward and backward pass (backward = 2x
    forward), with no recompute: matmuls, the depthwise convolution and the
    SSD mixer in its chunked form of chunk ``ssm_chunk``."""
    D, V, L = s["d_model"], s["vocab_size"], s["num_layers"]
    d_inner, H, N, conv_dim, d_in_proj = dims(s)
    P, W = s["ssm_head_dim"], s["ssm_conv_width"]
    c = min(s["ssm_chunk"], seq_len)
    per_token_layer = (
        2 * D * d_in_proj                    # in_proj
        + 2 * W * conv_dim                   # depthwise conv
        + 2 * c * N                          # C.B within a chunk
        + 2 * c * H * P                      # (C.B * decay) @ x within a chunk
        + 2 * 2 * H * N * P                  # chunk state in and state out
        + 2 * d_inner * D)                   # out_proj
    per_token = L * per_token_layer + 2 * D * V   # + output head
    return 3 * per_token * seq_len
