"""QSGD (Alistarh et al. 2017): unbiased stochastic rounding to
2^(bits-1) - 1 signed levels of a per-block max-abs scale."""
import jax
import jax.numpy as jnp

biased = False


def _block(n, opts):
    return max(1, min(opts.get("qsgd_block", 2048), n))


def roundtrip(x, key, arg, rest, opts):
    n, levels = x.shape[0], 2 ** (int(arg or 8) - 1) - 1
    b = _block(n, opts)
    nb = -(-n // b)
    xb = jnp.pad(x, (0, nb * b - n)).reshape(nb, b)
    scale = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
    u = jax.random.uniform(key, xb.shape, jnp.float32)
    q = jnp.floor(xb / jnp.maximum(scale, 1e-30) * levels + u)
    return rest((q / levels * scale).reshape(-1)[:n], key)


def payload(n, arg, fused, opts):
    """int8 codes (nibbles when packed) over whole blocks, f32 scales."""
    b = _block(n, opts)
    nb = -(-n // b)
    codes = -(-n // 2) if fused else nb * b
    return codes + 4 * nb, 0
