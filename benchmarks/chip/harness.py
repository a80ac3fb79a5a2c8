"""The benchmark's run of one cell, as functions that tests can call.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (the ``file`` of its entry in ``configs``) and a traffic mix
(``traffic/<traffic>.json``); its own numbers, the fixed arrival rate and
the limit of each number the correctness check compares, are in
``cells/<cell>.json``. The configuration names its block (``"block"``),
the module ``blocks/<block>.py`` that knows the architecture: the sizes the
program is checked against, the weights' layout, the plain reference's
forward and the operation counts (``blocks/dense.py`` says what each
gives). A run:

1. makes the weights on the device from the seed and builds the engine the
   way a user does: ``ContinuousBatchingEngine.from_artifact(..., paged=True)``
   on the default backend (``pallas-tpu`` on one chip), at the tensor-parallel
   width the configuration states (``tp``, ``tp_combine``; the weights are
   then made where the program places them, split over the chips);
2. warms every program the window uses (one prefill per power-of-two
   bucket the prompts and preemption resumes reach, and the decode step);
3. offers the mix's arrivals open-loop, due on the wall clock from
   ``-warm_in_s`` (so the window opens on a full batch) to ``seconds``;
   every request due is submitted before each ``engine.step()``, and each
   is timed from its due time by its own ``on_token`` callbacks;
4. drains what is in flight for at most ``drain_cap_s`` after the window;
5. reads the device's peak memory, frees the program, and checks a sample
   of the finished requests against the plain reference (``reference.py``).
"""
from __future__ import annotations

import contextlib
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import flops as F          # noqa: E402
import reference as R      # noqa: E402
import trace_reduce as T   # noqa: E402
import traffic as TR       # noqa: E402
import weights as W        # noqa: E402

#: seed offset of the draw that picks the requests to check
CHECK_SALT = 0x5EED


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def clock() -> float:
    # repro: allow-wallclock -- the benchmark times requests on the host
    return time.perf_counter()


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(path: str):
    """The Python file ``path``, loaded by its path (a block, a reader)."""
    name = os.path.splitext(os.path.basename(path))[0].replace(".", "_")
    spec = importlib.util.spec_from_file_location(
        f"bench_{os.path.basename(os.path.dirname(path))}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_block(conf: dict, where: str):
    """The block module that the configuration file ``where`` names
    (``"block": "<name>"``, the file ``blocks/<name>.py``). There is no
    default: a file that names no block, or one with no file, is refused."""
    name = conf.get("block")
    if not name:
        raise ValueError(f"{where}: names no block (\"block\": \"<name>\" "
                         f"for {os.path.join(HERE, 'blocks', '<name>.py')})")
    path = os.path.join(HERE, "blocks", name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"{where}: block {name!r} has no file {path}")
    return load_module(path)


def load_cell(name: str, bench: Optional[str] = None) -> dict:
    """The cell ``name`` with its configuration, block, mix and own
    numbers. A configuration's ``file`` is relative to ``bench``'s
    directory."""
    bench = bench or os.path.join(ROOT, "BENCHMARK.json")
    spec = _json(bench)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    conf_path = os.path.join(os.path.dirname(os.path.abspath(bench)),
                             configs[w["config"]]["file"])
    conf = _json(conf_path)
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    return {"name": name, "chips": w["chips"], "conf": conf,
            "block": load_block(conf, conf_path),
            "mix": _json(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
            "own": _json(os.path.join(HERE, "cells", name + ".json")),
            "end_to_end": e2e, "per_layer": per_layer}


def model_config(conf: dict, block):
    """The program's ModelConfig for a configuration file, checked against
    the sizes its block reads from the file's published ones (which the
    reference reads)."""
    from repro.configs import get_config

    cfg = get_config(conf["repro"]["config"]).with_overrides(
        **conf["repro"].get("overrides", {}))
    want = block.program_sizes(conf["model"])
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"program config departs from the file: {bad}")
    return cfg


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile (q in [0, 100]); 0.0 when empty."""
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else 0.0


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileCounter:
    """Counts programs lowered and compiled while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.on = False
        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name in self.counts:
            self.counts[name] += 1

    def total(self) -> int:
        return sum(self.counts.values())


def _engine_kwargs(cell: dict, backend) -> dict:
    eng, conf = cell["mix"]["engine"], cell["conf"]
    kw = dict(backend=backend, paged=True, n_slots=eng["n_slots"],
              max_len=eng["max_len"], block_size=eng["block_size"],
              kv_budget_bytes=conf["kv_budget_bytes"])
    if conf["tp"] > 1:
        # the engine swaps its backend for the ``-tp`` twin
        kw.update(tp=conf["tp"], tp_combine=conf["tp_combine"])
    return kw


def tp_shardings(abstract, conf: dict):
    """Where the program places each weight of ``abstract`` (the block's
    pytree as ``weights.abstract`` gives it) when it serves ``conf`` at
    ``conf["tp"]`` > 1 (its own ``tp_param_specs`` on its own serving
    mesh), so that the weights are made there and no leaf is ever whole on
    one chip; None at tp 1."""
    if conf["tp"] == 1:
        return None
    import jax
    from jax.sharding import NamedSharding

    from repro.launch.mesh import make_tp_mesh
    from repro.models.sharding import tp_param_specs

    mesh = make_tp_mesh(conf["tp"])
    specs = tp_param_specs(abstract, mesh, conf["tp_combine"])
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def reference_shardings(abstract, chips: int):
    """Where the reference's weights of ``abstract`` (the block's pytree
    as ``weights.abstract`` gives it) are made at ``chips`` > 1: every leaf
    of a stack (a top-level group, its leaves stacked on the layer axis)
    split on that axis over the cell's chips, the rest on each of them (the
    reference takes one layer at a time to the first chip); a stack whose
    depth the chips do not divide is held whole on each. None on one
    chip."""
    if chips == 1:
        return None
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("layers",))
    return jax.tree_util.tree_map_with_path(
        lambda path, a: NamedSharding(mesh, P("layers") if len(path) > 1
                                      and a.shape[0] % chips == 0 else P()),
        abstract)


def warm_lengths(mix: dict) -> List[int]:
    """Prompt lengths whose warm-up compiles every prefill bucket the mix
    can reach: the full-block part of a prompt of the mix's lengths, and of
    a preemption's resume (prompt + generated tokens, up to ``max_len``),
    padded to a power of two and capped at the cache length."""
    from repro.serving.kvcache import pow2_bucket

    eng = mix["engine"]
    bs, max_len = eng["block_size"], eng["max_len"]
    lo = mix["generator"]["prompt"].get("min", 1)
    top = max_len - bs                   # longest feed a resume can have
    buckets = sorted({min(pow2_bucket(max(((n - 1) // bs) * bs, 1)),
                          max_len)
                      for n in range(max(lo, bs + 1), top + 2, bs)})
    return [min(b, top) + 1 for b in buckets]


# ------------------------------------------------------------------ #
def build(cell: dict, seed: int, backend=None, log=print,
          variant: Optional[str] = None):
    """Weights from ``seed`` on the device, and the engine over them.
    ``variant`` names one of the program's own weight variants
    (``VariantSpec.<variant>()``, such as ``dynamic_int8``) to serve in
    place of the weights as made: the control of the correctness check."""
    import jax

    from repro.api import ContinuousBatchingEngine, ModelArtifact, VariantSpec

    conf, block = cell["conf"], cell["block"]
    cfg = model_config(conf, block)
    t = clock()
    params = W.make(block, conf["model"], seed, tp_shardings(
        W.abstract(block, conf["model"]), conf))
    jax.block_until_ready(params)
    log(f"weights: {cfg.name} from seed {seed} in {clock() - t:.3f} s")
    art = ModelArtifact.create(cfg.name, f"seed{seed}", params, cfg)
    del params
    if variant:
        vparams, _ = getattr(VariantSpec, variant)().build(art.params, cfg)
        art = art.with_variant(variant, vparams)
        del vparams
        log(f"weights: serving the program's {variant} variant")
    engine = ContinuousBatchingEngine.from_artifact(
        art, **_engine_kwargs(cell, backend))
    return cfg, engine


def warm(engine, mix: dict, log=print) -> None:
    """Compile every program the window runs: one warm-up request per
    prefill bucket (prefill and decode steps), and the scheduler's padding
    of a prompt's full-block part to its bucket, for every such part a
    prompt or a resume can have (one small program per length)."""
    import jax.numpy as jnp

    from repro.serving.kvcache import pow2_bucket

    t = clock()
    lengths = warm_lengths(mix)
    for n in lengths:
        engine.warmup(prompt_len=n)
    eng = mix["engine"]
    bs, max_len = eng["block_size"], eng["max_len"]
    lo = mix["generator"]["prompt"].get("min", 1)
    n_pad = 0
    # a prompt of at most one block is prefilled whole; a longer one up to
    # its last full block
    for c in list(range(lo, bs)) + list(range(bs, max_len - bs + 1, bs)):
        b = min(pow2_bucket(c), max_len)
        if b > c:
            jnp.pad(np.zeros((1, c), np.int32), ((0, 0), (0, b - c)))
            n_pad += 1
    log(f"warm-up: prompt lengths {lengths} and {n_pad} padding shapes in "
        f"{clock() - t:.3f} s")


class Spans:
    """Host spans into the profiler's trace, around the calls into each
    layer of the engine, plus what the per-layer metrics count per call."""

    def __init__(self, engine):
        import jax

        self.tracing = False
        self.decode_calls: List[List[int]] = []
        self.prefill_calls: List[int] = []
        ann = jax.profiler.TraceAnnotation

        def wrap(fn, name, record=None):
            def call(*args):
                if not self.tracing:
                    return fn(*args)
                if record is not None:
                    record(args)
                with ann(name):
                    return fn(*args)
            return call

        def rec_decode(_args):
            self.decode_calls.append(
                [r.cache_pos + 1 for r in engine.active if r is not None])

        def rec_prefill(args):
            self.prefill_calls.append(int(args[3]))

        engine._admit = wrap(engine._admit, "bench.admit")
        engine._decode_paged = wrap(engine._decode_paged, "bench.decode",
                                    rec_decode)
        engine._prefill_paged = wrap(engine._prefill_paged, "bench.prefill",
                                     rec_prefill)
        self.ann = ann

    def span(self, name):
        return self.ann(name) if self.tracing else contextlib.nullcontext()


# ------------------------------------------------------------------ #
def drive(engine, reqs: List[TR.Request], *, seconds: float, warm_s: float,
          drain_s: float, trace_s: float = 0.0, spans: Optional[Spans] = None,
          counter: Optional[CompileCounter] = None, trace_dir: str = "",
          log=print) -> dict:
    """Offer ``reqs`` open-loop and step the engine; returns every
    request's times and the window's counters. With ``trace_s``, the
    profiler traces the window's last ``trace_s`` seconds and stops once no
    request due in the window is queued, so that its stop, which holds the
    host for seconds, delays no request's admission."""
    import jax

    from repro.serving.sampling import SamplingParams

    n = len(reqs)
    tok_times: List[List[float]] = [[] for _ in range(n)]
    submitted: List[float] = [0.0] * n
    admitted: List[float] = [0.0] * n
    handles: list = [None] * n
    waiting: List[int] = []
    t0 = clock() + warm_s                     # the window opens here
    t_close = t0 + seconds
    i, stage = 0, "warm"
    snap: Dict[str, int] = {}
    alloc = engine.kv.alloc
    live: List[int] = []                  # slots in use after each step
    span = spans.span if spans is not None else (
        lambda _n: contextlib.nullcontext())
    traced_ann = None
    to_trace = trace_s > 0 and spans is not None

    def stop_trace():
        traced_ann.__exit__(None, None, None)
        spans.tracing = False
        jax.profiler.stop_trace()

    while True:
        now = clock()
        if stage == "warm" and now >= t0:
            stage = "window"
            snap["pre_open"] = engine.preempted_total
            snap["steps_open"] = engine.steps
            alloc.stats.peak_in_use = alloc.in_use
            if counter is not None:
                counter.on = True
        if to_trace and stage == "window" and now >= t_close - trace_s:
            to_trace = False
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            spans.tracing = True
            traced_ann = spans.ann("bench.traced")
            traced_ann.__enter__()
        if stage == "window" and now >= t_close:
            stage = "drain"
            snap["pre_close"] = engine.preempted_total
            snap["steps_close"] = engine.steps
            snap["kv_peak"] = alloc.stats.peak_in_use
            if counter is not None:
                counter.on = False
        if traced_ann is not None and stage == "drain" and not waiting:
            stop_trace()
            traced_ann = None
        with span("bench.submit"):
            while i < n and t0 + reqs[i].due <= now:
                r = reqs[i]
                sp = (SamplingParams() if r.greedy else SamplingParams(
                    temperature=r.temperature, top_k=r.top_k,
                    seed=r.idx))
                handles[i] = engine.submit(
                    r.prompt, r.max_new_tokens, sampling=sp,
                    on_token=lambda _q, _t, k=i: tok_times[k].append(
                        clock()))
                submitted[i] = clock()
                waiting.append(i)
                i += 1
        if engine.has_work:
            with span("bench.step"):
                engine.step()
            now = clock()
            still = []
            for k in waiting:
                if handles[k].status == "queued":
                    still.append(k)
                else:
                    admitted[k] = now
            waiting = still
            if stage == "window":
                live.append(sum(1 for r in engine.active if r is not None))
        elif i < n:
            with span("bench.wait"):
                time.sleep(max(0.0, min(t0 + reqs[i].due - clock(), 0.05)))
        elif stage == "drain":
            break
        if stage == "drain" and clock() >= t_close + drain_s:
            break
    if traced_ann is not None:
        stop_trace()
    t_end = clock()
    snap.setdefault("pre_close", engine.preempted_total)
    snap.setdefault("steps_close", engine.steps)
    snap.setdefault("kv_peak", alloc.stats.peak_in_use)
    return {"t0": t0, "t_close": t_close, "t_end": t_end,
            "tok_times": tok_times, "submitted": submitted,
            "admitted": admitted, "handles": handles, "snap": snap,
            "live": live}


def end_to_end(reqs, run: dict, seconds: float) -> dict:
    """TTFT, gaps between tokens and tokens per second, from due times,
    and the time per engine step over the window."""
    t0, t_close, t_end = run["t0"], run["t_close"], run["t_end"]
    attempted = [k for k, r in enumerate(reqs) if 0 <= r.due < seconds]
    ttft, failed = [], 0
    for k in attempted:
        h, times = run["handles"][k], run["tok_times"][k]
        due = t0 + reqs[k].due
        if h is None or h.rejected or not h.done:
            failed += 1
        # a request with no token counts as missing by the time the run
        # gave up on it
        ttft.append((times[0] if times else t_end) - due)
    gaps, n_tok = [], 0
    for times in run["tok_times"]:
        for j, t in enumerate(times):
            if t0 <= t < t_close:
                n_tok += 1
                if j:
                    gaps.append(t - times[j - 1])
    steps = run["snap"]["steps_close"] - run["snap"]["steps_open"]
    late = [run["submitted"][k] - (t0 + reqs[k].due)
            for k in range(len(reqs)) if run["handles"][k] is not None]
    return {"attempted": len(attempted), "failed": failed,
            "ttft_p90_s": percentile(ttft, 90),
            "ttft_p50_s": percentile(ttft, 50),
            "itl_p95_ms": percentile(gaps, 95) * 1e3,
            "itl_p50_ms": percentile(gaps, 50) * 1e3,
            "itl_p99_ms": percentile(gaps, 99) * 1e3,
            "itl_mean_ms": float(np.mean(gaps)) * 1e3 if gaps else 0.0,
            "output_tok_s": n_tok / seconds, "window_tokens": n_tok,
            "decode_step_ms": 1e3 * seconds / steps if steps else 0.0,
            "window_steps": steps,
            "n_gaps": len(gaps), "late_p50_ms": percentile(late, 50) * 1e3,
            "late_max_ms": max(late, default=0.0) * 1e3}


def pick_checked(reqs, run: dict, seed: int, k: int) -> List[int]:
    """A sample, drawn from the seed, of the attempted requests that
    finished, with the longest (prompt + served tokens) in it."""
    done = [i for i, r in enumerate(reqs)
            if run["handles"][i] is not None and run["handles"][i].done
            and r.due >= 0]
    if not done:
        return []
    longest = max(done, key=lambda i: (reqs[i].prompt.shape[1]
                                       + len(run["handles"][i].out_tokens),
                                       -i))
    rng = np.random.default_rng([abs(int(seed)), CHECK_SALT])
    rest = [i for i in done if i != longest]
    pick = list(rng.permutation(rest)[:max(0, k - 1)])
    return sorted([longest] + [int(i) for i in pick])


def check(cell: dict, reqs, served: Dict[int, list], seed: int,
          controls=(), log=print) -> dict:
    """The served tokens of the checked requests against the reference:
    the widest gap by which a served token's reference logit lies below
    the reference's best, and whether each request was served whole."""
    model, block, mix = cell["conf"]["model"], cell["block"], cell["mix"]
    t = clock()
    params = W.make(block, model, seed, reference_shardings(
        W.abstract(block, model), cell["chips"]))
    seqs = [(reqs[i].prompt, served[i]) for i in sorted(served)]
    out = R.gaps(params, block, model, seqs,
                 pad_to=mix["engine"]["max_len"],
                 read_len=mix["generator"]["output"]["max"],
                 controls=controls)
    del params
    every = np.concatenate([g for g, _ in out] + [np.zeros(0)])
    widest = float(every.max()) if len(every) else 0.0
    mean = float(every.mean()) if len(every) else 0.0
    ctl = {}
    for q in controls:
        c = np.concatenate([c[q] for _, c in out] + [np.zeros(0)])
        ctl[q] = {"widest_gap": float(c.max()) if len(c) else 0.0,
                  "mean_gap": float(c.mean()) if len(c) else 0.0}
    short = sum(1 for i in served
                if len(served[i]) != reqs[i].max_new_tokens)
    n_tok = sum(len(s) for s in served.values())
    log(f"reference: {len(seqs)} requests, {n_tok} served tokens checked in "
        f"{clock() - t:.3f} s")
    return {"widest_gap": widest, "mean_gap": mean, "control_gap": ctl,
            "short": short,
            "checked_tokens": n_tok, "checked_requests": len(seqs)}


def judged(nums: dict, limits: dict) -> bool:
    """Whether every number a cell compares lies within its limit: the
    rule for the program's gaps and for each control's alike."""
    return all(nums[k] <= v for k, v in limits.items())


def read_metrics(cell: dict, rec: dict) -> Dict[str, dict]:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell["per_layer"]:
        v = load_module(os.path.join(HERE, "metrics", m["name"] + ".py")
                        ).read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ #
def run(cell: dict, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, backend=None,
        controls=(), variant: Optional[str] = None, cache: bool = True,
        log: Callable = print) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax

    devices = jax.devices()
    chips = cell["chips"]
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform!r} device(s)")
    if cache:
        from repro.launch.compile_cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
        # keep the small programs too, so that a second run compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    mix, own = cell["mix"], cell["own"]
    cfg, engine = build(cell, seed, backend, log, variant)
    kv = engine.kv
    pool = kv.bytes_per_block * kv.alloc.n_blocks
    log(f"KV pool: {kv.alloc.n_blocks} blocks of {kv.block_size} tokens, "
        f"{pool / 1e9:.3f} GB")
    warm(engine, mix, log)
    reqs = TR.generate(mix["generator"], rate=own["rate_req_s"], seed=seed,
                       vocab=cfg.vocab_size, warm_s=mix["warm_in_s"],
                       window_s=seconds)
    counter = CompileCounter()
    spans = Spans(engine) if trace else None
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else ""
    res = drive(engine, reqs, seconds=seconds, warm_s=mix["warm_in_s"],
                drain_s=mix["drain_cap_s"],
                trace_s=min(mix.get("trace_s", seconds), seconds)
                if trace else 0.0,
                spans=spans, counter=counter, trace_dir=tdir, log=log)
    e2e = end_to_end(reqs, res, seconds)
    setup_s = res["t0"] - t_start            # process start to window open
    used = devices[:chips]
    peak = _peak_bytes(used)
    stats = [d.memory_stats() or {} for d in used]
    log("peak bytes in use per chip: "
        f"{[int(m.get('peak_bytes_in_use', 0)) for m in stats]} of "
        f"{[int(m.get('bytes_limit', 0)) for m in stats]}")
    snap = res["snap"]
    admitted_w = sum(1 for k, t in enumerate(res["admitted"])
                     if res["t0"] <= t < res["t_close"])
    preempted_w = snap["pre_close"] - snap["pre_open"]
    live = res["live"]
    log(f"requests: {len(reqs)} offered, {e2e['attempted']} due in the "
        f"window, {e2e['failed']} failed, {admitted_w} admitted in the "
        f"window, {preempted_w} preemptions, {e2e['window_steps']} steps in "
        f"the window ({e2e['decode_step_ms']:.3f} ms a step)")
    n_blocks = kv.alloc.usable_blocks
    log(f"occupancy in the window: KV pool peak {snap['kv_peak']} of "
        f"{n_blocks} blocks; slots in use mean "
        f"{np.mean(live) if live else 0.0:.2f}, max {max(live, default=0)} "
        f"of {len(engine.active)}")
    log(f"generator lateness: median {e2e['late_p50_ms']:.3f} ms, "
        f"max {e2e['late_max_ms']:.3f} ms")
    log(f"compiles inside the window: {counter.counts} (expected 0)")
    log(f"end to end: ttft p50 {e2e['ttft_p50_s']:.4f} s p90 "
        f"{e2e['ttft_p90_s']:.4f} s; itl p50 {e2e['itl_p50_ms']:.3f} ms "
        f"p95 {e2e['itl_p95_ms']:.3f} ms p99 {e2e['itl_p99_ms']:.3f} ms "
        f"mean {e2e['itl_mean_ms']:.3f} ms over {e2e['n_gaps']} gaps; "
        f"{e2e['output_tok_s']:.3f} tok/s; peak {peak} B")
    queue_wait = [res["admitted"][k] - (res["t0"] + r.due)
                  for k, r in enumerate(reqs)
                  if 0 <= r.due < seconds and res["admitted"][k] > 0]
    picked = pick_checked(reqs, res, seed, mix["check"]["sample"])
    served = {i: list(res["handles"][i].out_tokens) for i in picked}
    del engine, kv, res["handles"]
    gc.collect()
    jax.clear_caches()
    summary = None
    if trace:
        xp = [os.path.join(r, f) for r, _, fs in sorted(os.walk(tdir))
              for f in sorted(fs) if f.endswith(".xplane.pb")]
        if xp:
            summary = T.load(xp[0])
        shutil.rmtree(tdir, ignore_errors=True)
    chk = check(cell, reqs, served, seed, controls, log)
    # the numbers compared are those the cell's file gives a limit
    limits = own["limits"]
    correct = bool(served) and chk["short"] == 0 and judged(chk, limits)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": e2e["attempted"],
           "failed": e2e["failed"]}
    if not trace:
        vals = {"ttft_p90_s": e2e["ttft_p90_s"],
                "itl_p50_ms": e2e["itl_p50_ms"],
                "hbm_peak_gb": peak / 1e9, "setup_s": setup_s}
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in vals.items() if k in units}
    else:
        rec = {"model": cell["conf"]["model"], "block": cell["block"],
               "chips": chips,
               "kv_bytes": cell["conf"]["kv_dtype_bytes"],
               "peaks": F.peaks(dev.device_kind) if require_tpu else None,
               "trace": summary, "decode_calls": spans.decode_calls,
               "prefill_calls": spans.prefill_calls,
               "queue_wait_s": queue_wait, "admitted": admitted_w,
               "preempted": preempted_w, "kv_peak_blocks": snap["kv_peak"],
               "kv_blocks": n_blocks, "live_slots": live,
               "itl_gaps": e2e["n_gaps"], "itl_p95_ms": e2e["itl_p95_ms"]}
        out["metrics"] = read_metrics(cell, rec)
        if summary is not None:
            busy, window = T.busy_share(summary)
            device["busy_s"], device["window_s"] = busy, window
            out["breakdown"] = {"device_ops": T.top_ops(summary),
                                "idle_gaps": T.idle_gaps(summary)}
            log(f"trace: busy {busy:.6f} s of {window:.6f} s; idle by host "
                f"span {T.idle_by_label(summary)}")
    out["device"] = device
    if controls:
        out["control_gap"] = chk["control_gap"]
        # each control put in the program's place, judged as the program
        # is: it has to come out as not correct
        out["control_correct"] = {q: judged(g, limits)
                                  for q, g in chk["control_gap"].items()}
        for q, ok in out["control_correct"].items():
            log(f"control: {q} {chk['control_gap'][q]!r} "
                f"{'correct' if ok else 'not correct'}")
    # both gaps, whether or not the cell compares them (the readings limits
    # are set from)
    out["gaps"] = {k: chk[k] for k in ("widest_gap", "mean_gap")}
    out["check"] = {k: {"value": chk[k], "limit": v}
                    for k, v in limits.items()}
    out["check"]["short_requests"] = {"value": chk["short"], "limit": 0}
    log(f"check: {chk['checked_tokens']} served tokens of "
        f"{chk['checked_requests']} requests")
    for k, v in out["check"].items():
        log(f"check: {k} {v['value']!r} limit {v['limit']!r}")
    return out

