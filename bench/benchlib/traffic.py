"""The benchmark's traffic: seeded, non-iid federated language-model batches.

A copy of the program's generator (``repro.data.synthetic.sample_round``,
kept here so that a change to the program cannot move the yardstick): each
client samples token streams from its own bigram process
``softmax(G + beta (P_z + gamma_c))`` over a core vocabulary, and carries a
dataset size that weights it in FedAvg.  A traffic file gives the cohort,
the batch per client, the sequence length and the generator's parameters;
the seed gives everything else.

The seed reaches the compiled round at run time, through the round counter
the program feeds ``data_fn``: a run starts at round ``first_round(seed)``,
whose high bits name the seed's data stream and whose low ``ROUND_BITS``
count rounds.  So the compiled round is the same program for every seed and
the persistent compilation cache serves every run after the first.  Inputs besides tokens (a stub frontend's
frames) come from the model family's ``extra_inputs``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MASK32 = 0xFFFFFFFF
ROUND_BITS = 10            # rounds per data stream before it moves on
STREAM_BITS = 20           # data streams; the counter stays within int32


def seed_key(seed: int, stream: int):
    """A key for ``stream`` from a seed of up to 64 bits (``PRNGKey`` alone
    would drop the high half)."""
    k = jax.random.PRNGKey(seed & MASK32)
    return jax.random.fold_in(jax.random.fold_in(k, (seed >> 32) & MASK32),
                              stream)


def first_round(seed: int) -> int:
    """The round counter a run with ``seed`` starts from."""
    stream = (seed ^ (seed >> STREAM_BITS) ^ (seed >> 2 * STREAM_BITS)
              ^ (seed >> 3 * STREAM_BITS)) & ((1 << STREAM_BITS) - 1)
    return stream << ROUND_BITS


def _tables(key, C, V, gen):
    kg, kp, kz, ks, ku = jax.random.split(key, 5)
    G = jax.random.normal(kg, (V, V)) * 1.5
    P = jax.random.normal(kp, (gen["num_clusters"], V, V)) * 2.0
    z = jax.random.randint(kz, (C,), 0, gen["num_clusters"])
    gamma = jax.random.normal(ku, (C, V)) * 1.5 * gen["client_skew"]
    logits = G[None] + gen["heterogeneity"] * (P[z] + gamma[:, None, :])
    sizes = 1.0 + jax.random.uniform(ks, (C,))
    return logits, sizes


def _token_stream(lg, r, B, S, V):
    k0, kseq = jax.random.split(r)
    first = jax.random.randint(k0, (B,), 0, V)

    def step(tok, k):
        nxt = jax.random.categorical(k, lg[tok], axis=-1)
        return nxt, nxt
    _, toks = jax.lax.scan(step, first, jax.random.split(kseq, S))
    return toks.T


def make_data_fn(traffic: dict, sizes: dict, family, dtype):
    """``data_fn(round) -> batch`` (traceable): tokens, labels, mask (C, B,
    S), client sizes (C,), and the family's extra inputs in ``dtype``.  One
    round's batch depends on the round counter alone."""
    gen = traffic["generator"]
    C, B = traffic["clients"], traffic["batch_per_client"]
    S = traffic["seq_len"]
    V = min(sizes["vocab_size"], gen["core_vocab"])

    def data_fn(rnd):
        stream = jax.random.fold_in(jax.random.PRNGKey(0),
                                    rnd >> ROUND_BITS)
        k_tables, k_rounds = jax.random.split(stream)
        logits, client_sizes = _tables(k_tables, C, V, gen)
        r = jax.random.fold_in(k_rounds, rnd & ((1 << ROUND_BITS) - 1))
        k_tok, k_extra = jax.random.split(r)
        tokens = jax.vmap(lambda lg, k: _token_stream(lg, k, B, S, V))(
            logits, jax.random.split(k_tok, C))
        mask = jnp.ones((C, B, S), jnp.float32).at[:, :, -1].set(0.0)
        batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=-1),
                 "mask": mask, "sizes": client_sizes}
        for k, v in family.extra_inputs(k_extra, (C, B), sizes).items():
            batch[k] = v.astype(dtype)
        return batch

    return data_fn
