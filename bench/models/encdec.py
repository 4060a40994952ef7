"""Plain reference for the ``encdec`` family (Whisper-style), float32.

Written from the Whisper paper (arXiv:2212.04356) and the published
openai/whisper-base sizes; it imports nothing of ``repro``.  It follows the
program's layer equations where they depart from the published model, so
that a sound program agrees with it:

* the convolutional mel frontend is a stub: each utterance arrives as
  ``frontend_tokens`` frame embeddings of width ``d_model``;
* RMSNorm without bias in place of LayerNorm, and no projection biases;
* sinusoidal positions in the decoder as in the encoder (published: learned);
* GELU in its tanh form.

The output head is the token embedding's transpose where
``tie_embeddings`` is set, as published, and a leaf of its own otherwise.

Attention is a plain softmax over the whole sequence (the program tiles it
into chunks with an online softmax; nothing of that is shared here).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def layout(s):
    """``{path: (shape, init)}`` of every parameter leaf."""
    D, F, V = s["d_model"], s["d_ff"], s["vocab_size"]
    out = {"embed": ((V, D), "normal"), "final_ln": ((D,), "ones"),
           "encoder/final_ln": ((D,), "ones")}

    def attn(prefix, L):
        for w in ("wq", "wk", "wv", "wo"):
            out[prefix + w] = ((L, D, D), "normal")
        out[prefix + "ln"] = ((L, D), "ones")

    def mlp(prefix, L):
        out[prefix + "ln"] = ((L, D), "ones")
        out[prefix + "w_up"] = ((L, D, F), "normal")
        out[prefix + "w_down"] = ((L, F, D), "normal")

    if not s["tie_embeddings"]:
        out["lm_head"] = ((D, V), "normal")
    Le, Ld = s["encoder_layers"], s["num_layers"]
    attn("encoder/layers/mixer/", Le)
    mlp("encoder/layers/ffn/", Le)
    attn("layers/b0/mixer/", Ld)
    attn("layers/b0/cross/", Ld)
    mlp("layers/b0/ffn/", Ld)
    return out


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _sinusoid(n, d):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    ang = pos / (10_000.0 ** (2 * jnp.arange(d // 2, dtype=jnp.float32) / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def _attend(q, k, v, heads, causal):
    B, Sq, D = q.shape
    hd = D // heads
    q = q.reshape(B, Sq, heads, hd) * hd ** -0.5
    k = k.reshape(B, -1, heads, hd)
    v = v.reshape(B, -1, heads, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                      v).reshape(B, Sq, D)


def _self_attn(p, x, s, causal):
    h = _rmsnorm(x, p["ln"], s["norm_eps"])
    o = _attend(h @ p["wq"], h @ p["wk"], h @ p["wv"], s["num_heads"], causal)
    return x + o @ p["wo"]


def _mlp(p, x, s):
    h = _rmsnorm(x, p["ln"], s["norm_eps"])
    return x + jax.nn.gelu(h @ p["w_up"], approximate=True) @ p["w_down"]


def _group(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def hidden(params, batch, s):
    eps = s["norm_eps"]
    frames = batch["frontend"]
    x = frames + _sinusoid(frames.shape[1], s["d_model"]).astype(frames.dtype)
    enc = {"mixer": _group(params, "encoder/layers/mixer/"),
           "ffn": _group(params, "encoder/layers/ffn/")}

    @jax.checkpoint
    def enc_body(x, p):
        return _mlp(p["ffn"], _self_attn(p["mixer"], x, s, False), s), None

    x, _ = jax.lax.scan(enc_body, x, enc)
    enc_out = _rmsnorm(x, params["encoder/final_ln"], eps)

    tokens = batch["tokens"]
    y = params["embed"][tokens]
    y = y + _sinusoid(tokens.shape[1], s["d_model"]).astype(y.dtype)
    dec = {"mixer": _group(params, "layers/b0/mixer/"),
           "cross": _group(params, "layers/b0/cross/"),
           "ffn": _group(params, "layers/b0/ffn/")}

    @jax.checkpoint
    def dec_body(y, p):
        y = _self_attn(p["mixer"], y, s, True)
        c = p["cross"]
        h = _rmsnorm(y, c["ln"], eps)
        o = _attend(h @ c["wq"], enc_out @ c["wk"], enc_out @ c["wv"],
                    s["num_heads"], False)
        y = y + o @ c["wo"]
        return _mlp(p["ffn"], y, s), None

    y, _ = jax.lax.scan(dec_body, y, dec)
    return _rmsnorm(y, params["final_ln"], eps)


def loss(params, batch, s):
    """Mean next-token cross-entropy of the decoder over ``batch["mask"]``."""
    x = hidden(params, batch, s)
    head = params["embed"].T if s["tie_embeddings"] else params["lm_head"]
    logits = (x @ head).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)
    nll = jax.nn.logsumexp(logits, -1) - gold[..., 0]
    mask = batch["mask"]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def extra_inputs(key, lead, s):
    """The stub frontend's frame embeddings, N(0, 1), for ``lead`` utterances
    (a tuple of leading dims)."""
    return {"frontend": jax.random.normal(
        key, tuple(lead) + (s["frontend_tokens"], s["d_model"]), jnp.float32)}


def step_flops(s, seq_len):
    """FLOPs of one utterance's forward and backward pass (backward = 2x
    forward), with no recompute: every matmul and both attention products."""
    D, F, V, T = s["d_model"], s["d_ff"], s["vocab_size"], s["frontend_tokens"]
    S = seq_len
    enc = s["encoder_layers"] * T * (2 * 4 * D * D + 2 * 2 * D * F
                                     + 2 * 2 * T * D)
    dec = s["num_layers"] * (
        S * (2 * 4 * D * D + 2 * 2 * D * F + 2 * 2 * S * D)   # self + mlp
        + S * 2 * 2 * D * D + T * 2 * 2 * D * D               # cross q,o; k,v
        + 2 * 2 * S * T * D)                                  # cross products
    return 3 * (enc + dec + S * 2 * D * V)
