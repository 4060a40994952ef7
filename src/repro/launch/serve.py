"""Serving CLI: batched greedy decoding from a (trained or fresh) global
model — the downlink side of the FL story, and the driver behind the
decode_32k / long_500k dry-run shapes.

    PYTHONPATH=src python -m repro.launch.serve --arch paper_lm \
        --restore ckpt.npz --batch 4 --steps 32
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_lm")
    ap.add_argument("--restore", default="")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--window", type=int, default=0)
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    import jax.numpy as jnp
    from repro import checkpoint
    from repro.configs.registry import get_arch, get_smoke
    from repro.models.model import Model

    cfg = get_arch(args.arch) if args.arch == "paper_lm" \
        else get_smoke(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.restore:
        params = checkpoint.restore(args.restore, params)

    B = args.batch
    rng = jax.random.PRNGKey(1)
    prompt = jax.random.randint(rng, (B, args.prompt_len), 0, cfg.vocab_size)
    enc_len = cfg.frontend_tokens if cfg.family == "encdec" else 0
    cache = model.init_cache(B, args.cache_len, enc_len=enc_len)
    step = jax.jit(lambda p, c, t, pos: model.decode(
        p, c, t, pos, window=args.window))

    # prefill token-by-token (simple reference path), then greedy decode;
    # per-step wall-clock (block_until_ready) feeds the decode telemetry
    # summary below — the first step is the jit compile and is reported
    # separately, not folded into the latency stats
    import time
    tok = prompt[:, :1]
    out = [tok]
    prefill_s, decode_s = [], []
    for t in range(args.prompt_len + args.steps - 1):
        t0 = time.perf_counter()
        logits, cache = step(params, cache, tok, jnp.int32(t))
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        (prefill_s if t + 1 < args.prompt_len else decode_s).append(dt)
        if t + 1 < args.prompt_len:
            tok = prompt[:, t + 1:t + 2]
        else:
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out.append(tok)
    seqs = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} served {B} seqs x {seqs.shape[1]} tokens")
    for b in range(min(B, 2)):
        print(f"  seq{b}:", " ".join(str(int(x)) for x in seqs[b][:40]))

    # ------------------------------------------------- decode telemetry
    def _stats(xs):
        if not xs:
            return 0.0, 0.0
        xs = sorted(xs)
        mean = sum(xs) / len(xs)
        p95 = xs[min(len(xs) - 1, int(0.95 * (len(xs) - 1) + 0.5))]
        return mean, p95

    compile_s = prefill_s[0] if prefill_s else \
        (decode_s[0] if decode_s else 0.0)
    warm_prefill = prefill_s[1:]
    warm_decode = decode_s if prefill_s else decode_s[1:]
    pf_mean, pf_p95 = _stats(warm_prefill)
    dc_mean, dc_p95 = _stats(warm_decode)
    toks = B * len(warm_decode)
    wall = sum(warm_decode)
    print(f"decode telemetry: compile+first_step={compile_s * 1e3:.1f}ms")
    print(f"  prefill: {len(warm_prefill)} steps "
          f"mean={pf_mean * 1e3:.2f}ms p95={pf_p95 * 1e3:.2f}ms "
          f"({sum(warm_prefill):.3f}s total)")
    print(f"  decode:  {len(warm_decode)} steps "
          f"mean={dc_mean * 1e3:.2f}ms p95={dc_p95 * 1e3:.2f}ms "
          f"({wall:.3f}s total)")
    if wall > 0:
        print(f"  throughput: {toks / wall:.1f} tokens/sec "
              f"(batch {B} x {len(warm_decode)} warm decode steps)")


if __name__ == "__main__":
    main()
