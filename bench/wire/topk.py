"""Top-k sparsification by magnitude (Aji & Heafield 2017; DGC, STC).

Keeps the k = round(n * fraction) entries of largest |x|, ties to the lower
index, in descending order of |x| (the order the next stage sees them in).
The k-th magnitude is found by bisection on the float's bit pattern, which
reads the vector 32 times instead of sorting it; the kept entries are then
found by a binary search of their running count, not by a scatter.
"""
import jax
import jax.numpy as jnp

biased = True


def k_of(n, arg, opts):
    frac = float(arg) if arg else opts.get("topk_fraction", 0.01)
    return max(1, int(round(n * frac)))


def select(x, k):
    """(vals, idx): the top-k of |x|, largest first, ties by index."""
    bits = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32)

    def step(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        ok = jnp.sum(bits >= mid) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    t, _ = jax.lax.fori_loop(0, 32, step,
                             (jnp.int32(0), jnp.int32(0x7F800001)))
    above = bits > t
    ties = bits == t
    take = above | (ties & (jnp.cumsum(ties) <= k - jnp.sum(above)))
    # the j-th kept entry is the first whose running count reaches j
    idx = jnp.searchsorted(jnp.cumsum(take, dtype=jnp.int32),
                           jnp.arange(1, k + 1, dtype=jnp.int32))
    idx = idx.astype(jnp.int32)
    vals = x[idx]
    _, idx, vals = jax.lax.sort((-jnp.abs(vals), idx, vals), num_keys=1,
                                is_stable=True)
    return vals, idx


def roundtrip(x, key, arg, rest, opts):
    vals, idx = select(x, k_of(x.shape[0], arg, opts))
    return jnp.zeros_like(x).at[idx].set(rest(vals, key))


def payload(n, arg, fused, opts):
    """(bytes this stage puts on the wire, length of the carrier it passes
    on): int32 indices; the values travel on as the carrier."""
    k = k_of(n, arg, opts)
    return 4 * k, k
