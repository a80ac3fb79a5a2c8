#!/usr/bin/env python3
"""Chip smoke: serve stablelm-1.6b at its published widths through the paged
engine on one TPU chip, and check every variant against the XLA reference
on the same chip.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # mistral-nemo-12b, tp=4, four chips

The path is the one a user calls: ``repro.api.ModelArtifact`` ->
``ContinuousBatchingEngine.from_artifact(..., paged=True)`` ->
``PagedKVCache`` -> the ``pallas-tpu`` kernels. Weights are random, drawn
from ``--seed``; nothing is downloaded. Served variants: the KV tiers fp,
int8 and int4 with bf16 weights, then the ``dynamic_int8`` and
``static_int8`` weight variants that ``ArtifactRegistry.publish_variants``
builds, with fp KV. Each is served twice with the same requests: on the
default backend (``pallas-tpu``) and on an engine pinned to ``ref``. The
logits behind each request's first two tokens are compared.

Seconds and bytes printed on the way are the set-up and run time of this
smoke and the device's peak memory, not benchmark numbers. The last line of
stdout is ``{"ok": true, "device": {...}}``; any failure exits non-zero
without printing it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: (prompt tokens, new tokens) per request. Requests 3 and 4 share their
#: first PREFIX tokens, so request 4 is served from request 3's cached
#: blocks. Every cold prompt's full-block prefix (n - 1 rounded down to the
#: block size) falls in the 256-token prefill bucket, so each engine
#: compiles one prefill and one decode program.
REQUESTS = ((257, 32), (209, 16), (177, 24), (161, 20), (145, 28), (193, 16))
SHARED = (3, 4)
PREFIX = 128
BLOCK = 16
MAX_LEN = 512

#: Largest allowed max |kernel - ref| over the compared logits, as a
#: fraction of the largest |ref| logit. Both engines run the same bf16
#: model on the same chip and differ only in how attention (and, for the
#: weight variants, the int8 matmuls) is computed: f32 Pallas kernels
#: against XLA's ops. Every layer rounds its residual stream to bf16
#: (relative step 2^-8), so a difference of a few bf16 steps per layer
#: compounds over 24 layers into a few percent of the logit scale. The
#: quantized KV tiers re-quantize K/V computed from those slightly
#: different activations, so a code can land one step apart (1/127 of the
#: row's absmax for int8, 1/7 of a group's for int4): int4 gets twice the
#: room. The int8 weight variants re-quantize the input of every linear
#: layer (seven per layer; 1/127 of the row's absmax for dynamic, of a
#: calibrated per-tensor range for static), so an upstream difference that
#: moves an activation across a rounding boundary flips a whole code, and
#: the flips compound over the layers. Measured on a v5e chip with this
#: model: XLA's own matmul precision (default against highest) moves the
#: ref logits by 0.0165 of max|ref| with bf16 weights, 0.062 with dynamic
#: and 0.090 with static int8 weights. Each tolerance is about three times
#: that spread; the int8 kernels themselves are held to LINEAR_TOL on
#: identical inputs.
TOL = {"fp": 0.05, "int8": 0.05, "int4": 0.10,
       "dynamic_int8": 0.20, "static_int8": 0.30}

#: Largest allowed max |kernel - ref| / max |ref| of one quantized linear
#: layer on identical f32 inputs. Both sides round to the same int8 codes
#: and sum exactly in int32; only the order of the final f32 scale
#: products may differ, a few ulps (measured on a v5e chip: 0 for static,
#: 8.4e-8 for dynamic).
LINEAR_TOL = 1e-5


def make_prompts(vocab, requests=REQUESTS, prefix=PREFIX, seed=0):
    """Random prompts ``[1, n]`` int32; the SHARED requests start with the
    same ``prefix`` tokens."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, prefix)
    prompts = []
    for i, (n, _) in enumerate(requests):
        toks = rng.integers(0, vocab, n)
        if i in SHARED:
            toks[:prefix] = shared
        prompts.append(toks.astype(np.int32)[None])
    return prompts


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0)


def _step_programs(engine, bucket):
    """StableHLO of the engine's paged prefill and decode steps, traced as
    the engine traces them: same functions, its backend bound."""
    import jax
    import jax.numpy as jnp

    from repro.api import use_backend
    from repro.models import decode_step_paged, prefill_paged

    cfg, tpx, kv = engine.cfg, engine._tp_ctx, engine.kv
    if tpx is not None:
        pre, dec = tpx.prefill_paged, tpx.decode_step_paged
    else:
        def pre(p, c, b, nv, tb):
            return prefill_paged(p, c, b, nv, tb, cfg)

        def dec(p, c, t, pos, tb):
            return decode_step_paged(p, c, t, pos, tb, cfg)
    batch = {"tokens": jnp.zeros((1, bucket), jnp.int32)}
    with use_backend(engine.backend):
        return (jax.jit(pre).lower(engine.params, kv.pools, batch,
                                   jnp.int32(bucket), kv.tables[:1]).as_text(),
                jax.jit(dec).lower(engine.params, kv.pools,
                                   engine.last_tokens, engine.positions,
                                   kv.tables).as_text())


def serve(artifact, backend, prompts, requests, *, max_len=MAX_LEN, tp=1,
          tp_combine="exact", require_kernels=False):
    """Serve ``prompts`` greedily on a fresh paged engine. Returns the token
    streams, the logits behind each request's first two tokens, prefix-hit
    tokens, and set-up / run seconds."""
    import jax
    import jax.numpy as jnp

    from repro.api import ContinuousBatchingEngine
    from repro.serving.kvcache import pow2_bucket

    t0 = time.perf_counter()
    engine = ContinuousBatchingEngine.from_artifact(
        artifact, backend=backend, paged=True,
        n_slots=max(8, len(prompts)), max_len=max_len, block_size=BLOCK,
        tp=tp, tp_combine=tp_combine)
    longest = max(p.shape[1] for p in prompts)
    engine.warmup(prompt_len=longest)          # compiles prefill + decode
    t1 = time.perf_counter()
    if require_kernels:
        bucket = pow2_bucket(((longest - 1) // BLOCK) * BLOCK)
        for name, text in zip(("prefill", "decode"),
                              _step_programs(engine, bucket)):
            if "tpu_custom_call" not in text:
                raise RuntimeError(f"{name} program holds no Pallas kernel")

    # observe the logits each token was chosen from: the engine's step
    # entry points return them, and on_token fires right after the step
    last = [None]

    def recorded(fn, name):
        def call(*args):
            out = fn(*args)
            last[0] = (name, out[0])
            return out
        return call

    engine._prefill_paged = recorded(engine._prefill_paged, "prefill")
    engine._decode_paged = recorded(engine._decode_paged, "decode")
    logits = {}

    def on_token(i, req, tok):
        k = len(req.out_tokens) - 1
        if k < 2:
            name, out = last[0]
            row = 0 if name == "prefill" else next(
                s for s, r in enumerate(engine.active) if r is req)
            logits[i, k] = np.asarray(out[row, -1], np.float32)

    t2 = time.perf_counter()
    reqs = [engine.submit(jnp.asarray(p), n,
                          on_token=lambda r, t, i=i: on_token(i, r, t))
            for i, (p, (_, n)) in enumerate(zip(prompts, requests))]
    engine.run()
    jax.block_until_ready(engine.kv.pools)
    t3 = time.perf_counter()
    if not all(r.done for r in reqs):
        raise RuntimeError("engine left requests unfinished")
    return {"tokens": [list(r.out_tokens) for r in reqs], "logits": logits,
            "prefix_hit_tokens": engine.metrics()["prefix_hit_tokens"],
            "setup_s": t1 - t0, "run_s": t3 - t2}


def compare(kern, ref):
    """max |Δ| of the logits behind each request's first token (the
    prompt's last position) and, where both engines chose the same first
    token, behind its second (the first decode step), relative to the
    largest |ref| logit; plus the share of greedy tokens that agree up to
    each stream's first divergence."""
    n = len(kern["tokens"])
    scale = max(float(np.max(np.abs(ref["logits"][i, 0]))) for i in range(n))
    prompt = max(float(np.max(np.abs(kern["logits"][i, 0]
                                      - ref["logits"][i, 0])))
                 for i in range(n))
    same = [i for i in range(n)
            if kern["tokens"][i][0] == ref["tokens"][i][0]]
    decode = max((float(np.max(np.abs(kern["logits"][i, 1]
                                      - ref["logits"][i, 1])))
                  for i in same), default=0.0)
    agree = total = 0
    for a, b in zip(kern["tokens"], ref["tokens"]):
        total += len(b)
        for x, y in zip(a, b):
            if x != y:
                break
            agree += 1
    return {"max_abs_prompt": prompt, "max_abs_decode": decode,
            "decode_compared": len(same), "ref_scale": scale,
            "rel": max(prompt, decode) / scale,
            "greedy_agreement": agree / total}


def serve_and_compare(artifact, backend, ref_backend="ref", *,
                      requests=REQUESTS, prefix=PREFIX, max_len=MAX_LEN,
                      seed=0, tp=1, tp_combine="exact",
                      require_kernels=False):
    """Serve the same seeded requests on ``backend`` and on ``ref_backend``
    and compare them; returns ``(report, kernel run, ref run)``."""
    prompts = make_prompts(artifact.config.vocab_size, requests, prefix,
                           seed)
    kw = dict(max_len=max_len, tp=tp, tp_combine=tp_combine)
    kern = serve(artifact, backend, prompts, requests,
                 require_kernels=require_kernels, **kw)
    gc.collect()        # an engine holds reference cycles: free its pools
    ref = serve(artifact, ref_backend, prompts, requests, **kw)
    return compare(kern, ref), kern, ref


def linear_check(params, backend, ref_backend="ref", seed=0):
    """max |kernel - ref| / max |ref| of the first layer's quantized MLP
    input projection on one block of f32 activations: the int8 matmul
    kernels alone, on identical inputs."""
    import jax
    import jax.numpy as jnp

    from repro.api import use_backend
    from repro.models.layers import linear

    wi = jax.tree.map(lambda a: a[0], params["layers"]["mlp"]["wi"])
    x = jax.random.normal(jax.random.PRNGKey(seed),
                          (8, wi["w_int8"].shape[0]), jnp.float32)
    outs = []
    for b in (backend, ref_backend):
        with use_backend(b):
            outs.append(np.asarray(jax.jit(linear)(wi, x)))
    return float(np.max(np.abs(outs[0] - outs[1]))
                 / np.max(np.abs(outs[1])))


def _phase(name, tol, artifact, devices, failures, **kw):
    """One served variant: print its comparison, record a failure."""
    import jax

    try:
        report, kern, ref = serve_and_compare(artifact, **kw)
        ok = report["rel"] <= tol
        print(f"[{name}] max|dlogit| prompt {report['max_abs_prompt']:.5g} "
              f"decode {report['max_abs_decode']:.5g} "
              f"({report['decode_compared']}/{len(kern['tokens'])} first "
              f"decode steps compared) = {report['rel']:.5g} of max|ref| "
              f"{report['ref_scale']:.5g}; tolerance {tol} -> "
              f"{'within' if ok else 'EXCEEDED'}; greedy agreement "
              f"{report['greedy_agreement']:.4f}; prefix-hit tokens "
              f"{kern['prefix_hit_tokens']}", flush=True)
        print(f"[{name}] smoke seconds: kernel set-up {kern['setup_s']:.2f} "
              f"run {kern['run_s']:.2f}; ref set-up {ref['setup_s']:.2f} "
              f"run {ref['run_s']:.2f}; peak bytes in use "
              f"{[_peak_bytes(d) for d in devices]}", flush=True)
        if not ok:
            failures.append(f"{name}: {report['rel']:.5g} > {tol}")
        if kern["prefix_hit_tokens"] <= 0:
            failures.append(f"{name}: the shared prefix was not hit")
    except Exception:
        traceback.print_exc()
        failures.append(f"{name}: raised")
    jax.clear_caches()
    gc.collect()            # engines hold cycles: free their pools now


def _build(cfg, seed, devices, shardings=None, note=""):
    """Random params for ``cfg`` from ``seed``, made on the device (placed
    per ``shardings`` as they are made, where given), as a ModelArtifact."""
    import jax

    from repro.api import ModelArtifact
    from repro.models import init_params

    t = time.perf_counter()
    params = jax.jit(init_params, static_argnums=1,
                     out_shardings=shardings)(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    model = ModelArtifact.create(cfg.name, f"seed{seed}", params, cfg)
    print(f"[build] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, head_dim "
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, {model.size_bytes / 1e9:.3f} GB {cfg.dtype} "
          f"{note}; set-up {time.perf_counter() - t:.2f} s; peak bytes in "
          f"use {[_peak_bytes(d) for d in devices]}", flush=True)
    return model


def one_chip(seed, failures):
    import jax

    from repro.api import ArtifactRegistry, VariantSpec, current_backend
    from repro.configs import get_config

    dev = jax.devices()[0]
    model = _build(get_config("stablelm-1.6b"), seed, [dev])
    cfg = model.config
    backend = current_backend().name
    print(f"[serve] default backend: {backend}", flush=True)
    if backend != "pallas-tpu":
        failures.append(f"default backend is {backend}, not pallas-tpu")
        return
    kw = dict(backend=None, seed=seed, require_kernels=True)
    for prec in ("fp", "int8", "int4"):
        art = dataclasses.replace(
            model, config=cfg.with_overrides(kv_cache_precision=prec))
        _phase(f"kv={prec}", TOL[prec], art, [dev], failures, **kw)

    registry_dir = os.path.join(ROOT, ".smoke_registry")
    calib = [{"tokens": make_prompts(cfg.vocab_size, seed=seed)[0]}]
    for spec in (VariantSpec.dynamic_int8(),
                 VariantSpec.static_int8(calib_batches=1)):
        shutil.rmtree(registry_dir, ignore_errors=True)
        try:
            t = time.perf_counter()
            art = ArtifactRegistry(registry_dir).publish_variants(
                model, [spec], calib_data=calib)[spec.variant]
            print(f"[publish] {spec.variant}: {art.size_bytes / 1e9:.3f} "
                  f"GB; set-up {time.perf_counter() - t:.2f} s", flush=True)
            rel = linear_check(art.params, None)      # default backend
            print(f"[weights={spec.variant}] layer-0 mlp.wi on identical "
                  f"inputs: max|kernel - ref| = {rel:.3g} of max|ref|; "
                  f"tolerance {LINEAR_TOL} -> "
                  f"{'within' if rel <= LINEAR_TOL else 'EXCEEDED'}",
                  flush=True)
            if rel > LINEAR_TOL:
                failures.append(f"{spec.variant} linear: {rel:.3g} > "
                                f"{LINEAR_TOL}")
        except Exception:
            traceback.print_exc()
            failures.append(f"publish/linear check {spec.variant}: raised")
            continue
        finally:
            shutil.rmtree(registry_dir, ignore_errors=True)
        _phase(f"weights={spec.variant}", TOL[spec.variant], art, [dev],
               failures, **kw)
        del art


def four_chips(seed, failures):
    """mistral-nemo-12b at tp=4: params are built already sharded (jit with
    TP out_shardings), never whole on one device."""
    import jax
    from jax.sharding import NamedSharding

    from repro.configs import get_config
    from repro.launch.mesh import make_tp_mesh
    from repro.models import init_params
    from repro.models.sharding import tp_param_specs

    devices = jax.devices()[:4]
    cfg = get_config("mistral-nemo-12b")
    combine = "psum"        # wo row-sharded: "exact" replicates every wo
    mesh = make_tp_mesh(4)
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(lambda: init_params(key, cfg))
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             tp_param_specs(shapes, mesh, combine))
    model = _build(cfg, seed, devices, shardings, "sharded tp=4")
    _phase("tp=4", TOL["fp"], model, devices, failures,
           backend="pallas-tpu-tp", ref_backend="ref-tp", seed=seed, tp=4,
           tp_combine=combine, require_kernels=True)
    peaks = [_peak_bytes(d) for d in devices]
    print(f"[tp=4] peak bytes in use per device {peaks}", flush=True)
    if max(peaks) >= 16e9 or min(peaks) < 0.8 * max(peaks):
        failures.append(f"tp=4 per-device peaks {peaks}: not below 16 GB "
                        "and even")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="serve mistral-nemo-12b at tp=4 (and nothing else)")
    args = ap.parse_args(argv)

    import jax

    print(f"jax {jax.__version__}; devices {jax.devices()}", flush=True)
    dev = jax.devices()[0]
    need = 4 if args.four_chips else 1
    if dev.platform != "tpu" or len(jax.devices()) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX found "
              f"{len(jax.devices())} device(s) of platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache {cache}: {n_cached} entries before this run",
          flush=True)

    failures: list = []
    (four_chips if args.four_chips else one_chip)(args.seed, failures)
    n_after = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache {cache}: {n_after} entries after this run",
          flush=True)
    if failures:
        print("chip_smoke FAILED: " + "; ".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
