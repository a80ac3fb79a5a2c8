"""Serving launcher: load an artifact (or train a smoke model ad hoc) and
serve batched requests through the micro-batching queue.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --requests 32 --quant dynamic_int8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--quant", default="none",
                    choices=["none", "dynamic_int8", "static_int8"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for ad-hoc params and request payloads")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro import configs as C
    from repro.core.quant import QuantConfig, quantize_tree
    from repro.models import init_params
    from repro.serving import InferenceSession, Pipeline, RequestQueue
    from repro.training import load_checkpoint

    if args.checkpoint:
        params, cfg, _ = load_checkpoint(args.checkpoint)
    else:
        cfg = C.smoke_config(args.arch).with_overrides(dtype="float32")
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.quant != "none":
        params, paths = quantize_tree(
            params, QuantConfig(mode=args.quant, min_size=1024))
        print(f"quantized {len(paths)} weight tensors ({args.quant})")

    session = InferenceSession(params, cfg)
    pipe = Pipeline(
        preprocess=lambda b: b,
        infer=lambda b: session.generate(b, args.new_tokens),
        postprocess=lambda out, raw: out,
    )
    q = RequestQueue(pipe, max_batch=args.max_batch)

    key = jax.random.PRNGKey(args.seed)
    reqs = []
    for i in range(args.requests):
        key, sub = jax.random.split(key)
        payload = {"tokens": jax.random.randint(
            sub, (1, 16, cfg.n_codebooks) if cfg.n_codebooks > 1 else (1, 16),
            0, cfg.vocab_size)}
        if cfg.frontend != "none":
            payload["frontend_embeds"] = jax.random.normal(
                sub, (1, cfg.n_frontend_tokens, cfg.frontend_dim), jnp.float32)
        reqs.append(q.submit(payload))

    t0 = time.perf_counter()  # repro: allow-wallclock -- reported tok/s is real
    q.drain()
    dt = time.perf_counter() - t0  # repro: allow-wallclock -- interval vs t0
    assert all(r.done for r in reqs)
    print(f"served {len(reqs)} requests x {args.new_tokens} new tokens "
          f"in {dt:.2f}s ({len(reqs) * args.new_tokens / dt:.1f} tok/s), "
          f"mean session latency {session.stats.mean_ms:.1f} ms")


if __name__ == "__main__":
    main()
