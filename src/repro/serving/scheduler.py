"""Continuous batching decode scheduler v2 (vLLM-style, edge-sized).

A fixed pool of ``n_slots`` decode slots shares one batched KV cache; every
``step()`` decodes ALL occupied slots in a single jit-compiled decode_step
with per-slot positions (the vector-pos support in repro.models.attention).
Finished sequences free their slot immediately, so new requests join
mid-flight — no batch barrier.

v2 (serving as a first-class ``repro.api`` citizen):

* The engine serves a ``ModelArtifact`` / ``InferenceSession`` (or legacy
  ``(params, cfg)``) and pins a kernel ``Backend`` from the registry at
  trace time, so an int8-Pallas engine and an fp32 engine coexist in one
  process with independently compiled entry points.
* Chunked prefill: only the first ``prefill_chunk`` prompt tokens run
  through the batch-1 prefill; the remainder of the prompt rides the
  *batched* decode step, one token per tick, interleaved with every active
  slot's decode — a long prompt no longer stalls in-flight generation.
  ``prefill_chunk=0`` (default) prefills whole prompts in one shot, which is
  bit-identical to ``InferenceSession.generate``.
* Per-request ``SamplingParams`` (greedy / temperature / top-k), seeded per
  token index so output never depends on batch composition or slot layout.
* Per-slot EOS, including per-codebook EOS tuples for multi-codebook models.
* Streaming: ``submit(..., on_token=fn)`` fires per generated token.
* Admission control: priority scheduling plus ``max_queue_depth`` with
  rejection accounting, surfaced through the stable ``metrics()`` schema.

KV-cache v2 (``paged=True``):

* The dense ``(n_slots, max_len)`` cache is replaced by a block pool +
  ``BlockAllocator`` (``repro.serving.kvcache``): admission is by *free
  blocks* instead of free slots, HBM scales with tokens actually held, and
  identical prompt prefixes share refcounted blocks.
* Prefix-hit fast path: full prompt blocks found in the allocator's hash
  registry are attached (no recompute); only the un-cached tail of the
  prompt runs, riding the batched decode step.
* Cold prompts dense-prefill their full-block prefix in one shot (padded to
  a power-of-two bucket), scatter into fresh blocks, and register the block
  hashes for future reuse; the sub-block tail rides decode so a later
  prefix-hit replay is byte-identical to the cold run.
* Preemption-on-exhaustion: when the pool runs dry mid-decode the
  youngest/lowest-priority request is evicted back to the queue and later
  resumes by re-prefilling prompt + generated-so-far (token-identical to an
  uninterrupted run — greedy is exact argmax and sampling is seeded per
  token index).
* Dense mode stays the default compat path; paged is selected per engine.

Speculative decoding (``spec=SpecConfig(...)``, serving v3):

* A draft ``InferenceSession``-style model (any registry variant —
  ``int8_dynamic`` by default) proposes ``k`` tokens per step from its own
  dense per-slot cache; the target scores all ``k+1`` positions in ONE
  ``verify_step`` / ``verify_step_paged`` pass and the engine commits the
  longest agreed prefix plus one target token (correction or bonus).
* Greedy output is bit-identical to the target's baseline ``generate``
  regardless of draft quality; temperature>0 uses seeded rejection
  sampling keyed per generated-token index, so accepted streams stay
  batch-composition-independent (``repro.serving.spec_decode``).
* Rollback: dense caches roll back by position bookkeeping alone (stale
  verify writes are masked and overwritten); paged engines additionally
  truncate each slot's block table and free tail blocks that only held
  rejected tokens (``PagedKVCache.truncate``) so pool accounting never
  counts dead speculation.
* Prompt feeds (chunked-prefill tails, prefix-hit tails, preemption
  resume) ride the same verify pass — up to ``k+1`` known tokens are
  force-fed per step, so spec engines consume prompt tails faster than
  the one-token-per-tick dense path.

Deterministic and thread-free, like the rest of the serving layer.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.models import (decode_step, decode_step_paged, init_cache, prefill,
                          prefill_paged, verify_step, verify_step_paged)
from repro.models.config import ModelConfig
from repro.serving.engine import interpolated_percentile
from repro.serving.kvcache import (KVHandoff, PagedKVCache, SharedKVPool,
                                   bucketed_prefill_ok, hash_prompt_blocks,
                                   paged_supported, pow2_bucket)
from repro.serving.sampling import SamplingParams, sample
from repro.serving.spec_decode import (SpecConfig, draft_propose,
                                       greedy_accept, rejection_sample,
                                       spec_supported)

#: every metrics() call returns exactly these keys (schema-stable for the
#: BENCH_*.json pipeline — see benchmarks/report.py and DESIGN.md §Serving v2)
METRIC_KEYS = (
    "completed", "rejected", "queued", "active", "submitted",
    "decode_steps", "generated_tokens", "prefill_tokens",
    "mean_ttft_s", "p50_ttft_s", "p90_ttft_s", "p99_ttft_s",
    "mean_latency_s", "throughput_tok_s",
    # KV-cache v2 (zero for dense engines unless noted)
    "preempted",                 # requests evicted back to the queue
    "cancelled",                 # requests withdrawn via cancel()
    "prefix_hit_tokens",         # prompt tokens served from cached blocks
    "prefix_hit_rate",           # hit tokens / submitted prompt tokens
    "prompt_tokens_computed",    # prompt tokens actually recomputed
    "kv_blocks_peak",            # allocator high-water mark (paged)
    "kv_hbm_bytes_per_req",      # peak cache HBM / n_slots (dense + paged)
    # tensor-parallel serving (== kv_hbm_bytes_per_req when tp == 1)
    "tp",                        # model-axis shard count of this engine
    "kv_hbm_bytes_per_req_per_shard",  # per-chip share of the KV footprint
    # speculative decoding (zero for non-spec engines)
    "spec_events",               # per-slot draft/verify acceptance rounds
    "spec_draft_tokens",         # draft tokens proposed
    "spec_accepted_tokens",      # draft tokens accepted AND committed
    "acceptance_rate",           # accepted / proposed draft tokens
    "accepted_tokens_per_step",  # committed tokens per verify round (>1 good)
    "host_syncs",                # device values read into Python
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine-level knobs that travel as one value (fleet profiles, bench
    configs). ``ContinuousBatchingEngine(..., config=EngineConfig(tp=2))``
    turns on tensor-parallel serving with no other call-site changes;
    explicit keyword arguments win over the config's fields."""
    tp: int = 1                    # model-axis shards (1 = unsharded)
    tp_combine: str = "exact"      # "exact" (bit-identical) | "psum"
    backend: Optional[str] = None  # compute backend name to pin


@dataclasses.dataclass
class GenRequest:
    rid: int
    tokens: jax.Array                  # [1, S_prompt] (or [1,S,K] audio)
    max_new_tokens: int
    frontend_embeds: Optional[jax.Array] = None
    eos_id: Union[int, Sequence[int]] = -1   # -1: no EOS; tuple: per-codebook
    out_tokens: Optional[List[int]] = None
    done: bool = False
    submitted_at: float = 0.0
    admitted_at: float = 0.0           # first took a slot (kept on resume)
    first_token_at: float = 0.0
    finished_at: float = 0.0
    # v2 fields
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    priority: int = 0
    on_token: Optional[Callable[["GenRequest", Any], None]] = None
    status: str = "queued"   # queued|rejected|cancelled|prefill|decode|done
    n_consumed: int = 0                # prompt tokens already in the cache
    # disaggregated serving (paged engines sharing a SharedKVPool)
    capture_kv: bool = False           # prefill worker: export blocks on done
    kv_handoff: Optional[KVHandoff] = None      # the exported handoff
    _handoff: Optional[KVHandoff] = None        # incoming handoff to consume
    # KV-cache v2 fields (paged engines)
    prefix_hit: int = 0                # prompt tokens attached from cache
    preemptions: int = 0
    cache_pos: int = 0                 # next cache write position (host int)
    _admit_tokens: Optional[jax.Array] = None   # resume feed (prompt + gen)
    _resume_last: Any = None           # last generated token pre-preemption
    _block_hashes: Optional[List[int]] = None   # feed hash chain (cached)
    # speculative decoding (spec engines only)
    spec_events: int = 0               # verify rounds this request ran
    spec_accepted: int = 0             # draft tokens accepted + committed
    _spec_pending: Optional[List[int]] = None   # committed tokens the DRAFT
    # cache still lacks (normally derived as [last]; two entries right
    # after a fully-accepted round emitted a bonus token)

    @property
    def prompt_len(self) -> int:
        return self.tokens.shape[1]

    @property
    def feed_tokens(self) -> jax.Array:
        """Tokens driving prefill / decode-tail: the original prompt, or
        prompt + already-generated tokens after a preemption resume."""
        return (self._admit_tokens if self._admit_tokens is not None
                else self.tokens)

    @property
    def feed_len(self) -> int:
        return self.feed_tokens.shape[1]

    @property
    def rejected(self) -> bool:
        return self.status == "rejected"


def _tree_insert(batched, single, slot: int):
    """Write a batch-1 cache pytree into slot ``slot`` of the batched cache.

    Cache leaves are [L, B, ...]; single leaves are [L, 1, ...]."""
    return jax.tree.map(
        lambda c, u: jax.lax.dynamic_update_slice_in_dim(c, u.astype(c.dtype),
                                                         slot, axis=1),
        batched, single)


def _hits_eos(token, eos_id) -> bool:
    """token: int or [K] list; eos_id: -1 (never), int (codebook 0), or a
    per-codebook sequence (all codebooks must match)."""
    if isinstance(eos_id, (list, tuple)):
        toks = token if isinstance(token, list) else [token]
        return len(toks) == len(eos_id) and all(
            t == e for t, e in zip(toks, eos_id))
    if eos_id < 0:
        return False
    first = token[0] if isinstance(token, list) else token
    return first == eos_id


class ContinuousBatchingEngine:
    """``model`` may be a ``repro.api.ModelArtifact``, an
    ``InferenceSession`` (its pinned backend is inherited), or a raw params
    pytree with ``cfg`` passed separately (legacy signature)."""

    def __init__(self, model, cfg: Optional[ModelConfig] = None,
                 n_slots: int = 4, max_len: int = 512, *,
                 backend=None, prefill_chunk: int = 0,
                 max_queue_depth: int = 0,
                 paged: bool = False, block_size: int = 16,
                 n_blocks: Optional[int] = None,
                 kv_budget_bytes: Optional[int] = None,
                 spec: Optional[SpecConfig] = None,
                 tp: int = 1, tp_combine: str = "exact",
                 shared_kv: Optional[SharedKVPool] = None,
                 config: Optional["EngineConfig"] = None):
        # local import: repro.api pulls the fleet stack which imports
        # serving — resolve lazily to stay acyclic (same as engine.py)
        from repro.api.backends import TPBackend, get_backend, use_backend
        from repro.serving.engine import InferenceSession

        if config is not None:
            if tp == 1:
                tp = config.tp
            if tp_combine == "exact":
                tp_combine = config.tp_combine
            if backend is None:
                backend = config.backend

        if isinstance(model, InferenceSession):
            params, cfg = model.params, model.cfg
            backend = backend if backend is not None else model.backend
        elif hasattr(model, "params") and hasattr(model, "config"):
            params, cfg = model.params, model.config       # ModelArtifact
        else:
            if cfg is None:
                raise TypeError(
                    "ContinuousBatchingEngine(params, cfg) requires a "
                    "ModelConfig when given a raw params pytree")
            params = model
        self.params = params
        self.cfg = cfg
        self.backend = get_backend(backend) if backend is not None else None
        # tensor-parallel serving: a pinned *-tp backend opts in at its
        # default width; an explicit tp=N shards with the matching twin of
        # whatever compute backend is pinned (no call-site changes — the
        # shard_map wrapping happens at the bind sites below)
        if isinstance(self.backend, TPBackend) and tp == 1:
            tp = self.backend.default_tp
        if tp > 1 and self.backend is not None \
                and not isinstance(self.backend, TPBackend):
            from repro.api.backends import available_backends

            twin = f"{self.backend.name}-tp"
            if twin in available_backends():
                self.backend = get_backend(twin)
        self.tp = tp
        if tp > 1:
            from repro.serving.sharded import TPContext

            self._tp_ctx: Optional[TPContext] = TPContext(
                cfg, tp, combine=tp_combine, params=params)
            self.params = params = self._tp_ctx.shard_params(params)
        else:
            self._tp_ctx = None
        self.n_slots = n_slots
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.max_queue_depth = max_queue_depth
        self.paged = paged
        self.spec = spec
        self.spec_k = 0
        self._spec_m = 1               # verify span (k + 1) for spec engines
        if spec is not None:
            draft_params, draft_cfg, draft_backend = spec.resolve_draft()
            why = spec_supported(cfg, draft_cfg, spec.k,
                                 allow_moe_target=spec.allow_moe_target)
            if why is not None:
                raise ValueError(f"speculative decoding unsupported: {why}")
            self.spec_k = spec.k
            self._spec_m = spec.k + 1
            self.draft_params = draft_params
            self.draft_cfg = draft_cfg
            self.draft_backend = (get_backend(draft_backend)
                                  if draft_backend is not None
                                  else self.backend)
        # cache length: max_len plus verify-span headroom so speculative
        # writes near the sequence cap never clamp into valid rows
        self._pad_len = max_len + (self._spec_m if spec is not None else 0)
        self.positions = jnp.zeros((n_slots,), jnp.int32)
        self.active: List[Optional[GenRequest]] = [None] * n_slots
        self.last_tokens = (jnp.zeros((n_slots, 1, cfg.n_codebooks), jnp.int32)
                            if cfg.n_codebooks > 1
                            else jnp.zeros((n_slots, 1), jnp.int32))
        self._pending: List[Tuple[int, int, GenRequest]] = []  # heap
        self.all_requests: List[GenRequest] = []
        self._next_rid = 0
        self.steps = 0
        self.rejected_total = 0
        self.prefill_tokens = 0        # prompt tokens processed by prefill
        self.preempted_total = 0
        self.host_syncs = 0            # device values read into Python
        self.cancelled_total = 0
        self.prefix_hit_tokens = 0
        self.prompt_tokens_computed = 0
        self.prompt_tokens_submitted = 0
        if shared_kv is not None and not paged:
            raise ValueError("shared_kv requires paged=True")
        if paged:
            why = paged_supported(cfg)
            if why is not None:
                raise ValueError(
                    f"paged=True unsupported for {cfg.name}: {why} "
                    "(use the dense compat path)")
            if shared_kv is not None:
                # disaggregated serving: this engine attaches to a pool some
                # peer engine also serves from — block ids are shared, so
                # geometry comes from the store, not the local arguments
                if shared_kv.shards != self.tp:
                    raise ValueError(
                        f"shared pool built for shards={shared_kv.shards}, "
                        f"engine has tp={self.tp}")
                block_size = shared_kv.block_size
                n_blocks = shared_kv.alloc.n_blocks
            max_blocks = -(-self._pad_len // block_size)
            if n_blocks is None:
                if kv_budget_bytes is not None:
                    from repro.serving.kvcache import blocks_for_budget

                    # budget-sized pool, capped at full capacity (a huge
                    # budget must not allocate pools past what n_slots *
                    # max_len sequences could ever touch). The budget is
                    # per *device*: under tp each shard holds only its
                    # kv-head slice of a block, so the same budget admits
                    # more blocks (shards= divisor; MLA pools replicate)
                    n_blocks = min(blocks_for_budget(cfg, block_size,
                                                     kv_budget_bytes,
                                                     shards=self.tp),
                                   n_slots * max_blocks + 1)
                else:
                    # full budget: every slot can hold a max-length sequence
                    n_blocks = n_slots * max_blocks + 1
            self.kv: Optional[PagedKVCache] = PagedKVCache(
                cfg, n_slots, n_blocks, block_size, max_blocks,
                shards=self.tp,
                pool_sharding=(self._tp_ctx.shard_cache
                               if self._tp_ctx is not None else None),
                shared=shared_kv)
            self.cache = self.kv.pools          # alias: pools ARE the cache
        else:
            self.kv = None
            self.cache = init_cache(cfg, n_slots, self._pad_len)
            if self._tp_ctx is not None:
                self.cache = self._tp_ctx.shard_cache(self.cache)
        # jit entry points (shapes fixed by the slot pool), traced with this
        # engine's backend in scope so the kernel choice is baked in;
        # draft=True binds the draft model's backend instead. ``name`` is
        # the program's name in a profile (``jit_<name>``)
        def bind(name, fn, *, draft=False, **jit_kw):
            fn.__name__ = fn.__qualname__ = name
            jitted = jax.jit(fn, **jit_kw)

            @functools.wraps(jitted)
            def call(*args):
                with use_backend(self.draft_backend if draft
                                 else self.backend):
                    return jitted(*args)

            return call

        # with tp > 1 the model entry points are the shard-mapped twins
        # (TPContext methods: same arities, cfg + mesh captured) — every
        # call site below stays identical
        tpx = self._tp_ctx
        if tpx is not None:
            self._decode = bind("decode_step", lambda p, c, t, pos:
                                tpx.decode_step(p, c, t, pos))
            self._prefill = bind("prefill", lambda p, b, nv:
                                 tpx.prefill(p, b, nv, pad_to=self._pad_len))
        else:
            self._decode = bind(
                "decode_step",
                lambda p, c, t, pos: decode_step(p, c, t, pos, cfg))
            # ``nv`` (traced int32) marks the true token count: _admit_dense
            # bucket-pads the token axis (where bucketed_prefill_ok allows)
            # so distinct prompt lengths share one compiled prefill per
            # bucket
            self._prefill = bind(
                "prefill",
                lambda p, b, nv: prefill(p, b, cfg, pad_to=self._pad_len,
                                         n_valid=nv))
        if spec is not None:
            dcfg = self.draft_cfg
            # the draft keeps a dense per-slot cache even under a paged
            # target (ROADMAP follow-up: draft KV sharing); the last row is
            # a scratch position where idle/prefill slots' batched draft
            # writes land harmlessly
            self.draft_cache = init_cache(dcfg, n_slots, self._pad_len)
            self.draft_positions = jnp.zeros((n_slots,), jnp.int32)
            self._draft_trash = self._pad_len - 1
            self._draft_decode = bind(
                "draft_decode_step",
                lambda p, c, t, pos: decode_step(p, c, t, pos, dcfg),
                draft=True)
            self._draft_prefill = bind(
                "draft_prefill",
                lambda p, b, nv: prefill(p, b, dcfg, pad_to=self._pad_len,
                                         n_valid=nv),
                draft=True)
            if tpx is not None:
                self._verify = bind("verify_step", lambda p, c, t, pos:
                                    tpx.verify_step(p, c, t, pos))
            else:
                self._verify = bind(
                    "verify_step",
                    lambda p, c, t, pos: verify_step(p, c, t, pos, cfg))
            if paged:
                if tpx is not None:
                    self._verify_paged = bind(
                        "verify_step_paged",
                        lambda p, c, t, pos, tabs: tpx.verify_step_paged(
                            p, c, t, pos, tabs))
                else:
                    self._verify_paged = bind(
                        "verify_step_paged",
                        lambda p, c, t, pos, tabs: verify_step_paged(
                            p, c, t, pos, tabs, cfg))
        self.spec_events = 0           # per-slot verify acceptance rounds
        self.spec_committed = 0        # tokens committed by those rounds
        self.draft_proposed = 0
        self.draft_accepted = 0
        if paged:
            if tpx is not None:
                self._decode_paged = bind(
                    "decode_step_paged",
                    lambda p, c, t, pos, tabs: tpx.decode_step_paged(
                        p, c, t, pos, tabs))
                self._prefill_paged = bind(
                    "prefill_paged",
                    lambda p, c, b, nv, tabs: tpx.prefill_paged(
                        p, c, b, nv, tabs))
            else:
                self._decode_paged = bind(
                    "decode_step_paged",
                    lambda p, c, t, pos, tabs: decode_step_paged(
                        p, c, t, pos, tabs, cfg))
                # cold prefill scatters K/V straight into the block pools
                # through the slot's table (no dense single-request cache);
                # tokens are bucket-padded where the arch allows, so one
                # compile per bucket instead of one per distinct prompt
                # length
                self._prefill_paged = bind(
                    "prefill_paged",
                    lambda p, c, b, nv, tabs: prefill_paged(p, c, b, nv,
                                                            tabs, cfg))

    # ---------------------------------------------------------------- #
    @classmethod
    def from_artifact(cls, artifact, backend=None,
                      **kw) -> "ContinuousBatchingEngine":
        return cls(artifact, backend=backend, **kw)

    @property
    def queue_depth(self) -> int:
        # cancelled requests stay heap entries until lazily drained by
        # _admit — they must not count against admission backpressure
        return sum(1 for _, _, r in self._pending if r.status != "cancelled")

    @property
    def has_work(self) -> bool:
        return (any(r.status != "cancelled" for _, _, r in self._pending)
                or any(r is not None for r in self.active))

    def warmup(self, prompt_len: int = 0, max_new_tokens: int = 2) -> None:
        """Trace + compile the prefill/decode entry points with a throwaway
        request, then reset all counters, so wall-clock metrics measure
        steady-state serving instead of jax.jit compile time (benchmarks
        call this before replaying a trace). ``prompt_len`` defaults to the
        prefill chunk size — the batch-1 prefill shape real chunked
        requests hit."""
        s = prompt_len or self.prefill_chunk or 8
        shape = ((1, s, self.cfg.n_codebooks) if self.cfg.n_codebooks > 1
                 else (1, s))
        self.submit(jnp.zeros(shape, jnp.int32), max_new_tokens)
        self.run()
        self.all_requests.clear()
        self.steps = 0
        self.prefill_tokens = 0
        self.rejected_total = 0
        self.preempted_total = 0
        self.host_syncs = 0
        self.cancelled_total = 0
        self.prefix_hit_tokens = 0
        self.prompt_tokens_computed = 0
        self.prompt_tokens_submitted = 0
        self.spec_events = 0
        self.spec_committed = 0
        self.draft_proposed = 0
        self.draft_accepted = 0
        if self.paged:
            # drop the warmup request's registered blocks + allocator stats
            # so measurement runs start truly cold
            self.kv.reset()

    # ---------------------------------------------------------------- #
    def submit(self, tokens, max_new_tokens: int = 16,
               frontend_embeds=None, eos_id: Union[int, Sequence[int]] = -1,
               sampling: Optional[SamplingParams] = None, priority: int = 0,
               on_token: Optional[Callable] = None) -> GenRequest:
        """Queue a request. Higher ``priority`` admits first (FIFO within a
        priority level). When the queue already holds ``max_queue_depth``
        requests the submission is REJECTED: ``req.status == "rejected"``,
        never scheduled, counted in ``metrics()["rejected"]``."""
        req = GenRequest(self._next_rid, tokens, max_new_tokens,
                         frontend_embeds, eos_id, out_tokens=[],
                         # repro: allow-wallclock -- TTFT/e2e gates measure real compute
                         submitted_at=time.perf_counter(),
                         sampling=sampling or SamplingParams(),
                         priority=priority, on_token=on_token)
        self._next_rid += 1
        self.all_requests.append(req)
        if self.max_queue_depth and len(self._pending) >= self.max_queue_depth:
            req.status = "rejected"
            self.rejected_total += 1
            return req
        if self.paged:
            # memory-based admission: a request that could NEVER fit the
            # pool (even alone, with every cached block evicted) is
            # rejected up front rather than starving the queue
            total = (self.cfg.n_frontend_tokens + req.prompt_len
                     + max_new_tokens)
            if (total > self.max_len
                    or self.kv.blocks_for_tokens(total) + 1
                    > self.kv.alloc.usable_blocks):
                req.status = "rejected"
                self.rejected_total += 1
                return req
        self.prompt_tokens_submitted += req.prompt_len
        heapq.heappush(self._pending, (-priority, req.rid, req))
        return req

    # ---------------------------------------------------------------- #
    # Disaggregated serving entry points (paged engines on a SharedKVPool)
    # ---------------------------------------------------------------- #
    def submit_prefill(self, tokens, sampling: Optional[SamplingParams] = None,
                       priority: int = 0,
                       on_token: Optional[Callable] = None) -> GenRequest:
        """Queue a prompt on a dedicated *prefill worker*: the engine
        computes the prompt's paged KV plus exactly one generated token,
        then — instead of dropping the blocks at release — exports them as
        ``req.kv_handoff`` for a decode worker sharing the same pool.
        Every full prompt block is also hash-registered, so the computed
        prefix survives as cache even if the handoff is never consumed."""
        if not self.paged:
            raise ValueError("submit_prefill requires a paged engine")
        if self.spec is not None:
            raise ValueError("prefill workers do not run speculative decode")
        if self.cfg.n_frontend_tokens:
            raise ValueError("frontend-token archs cannot hash prompt blocks")
        req = self.submit(tokens, max_new_tokens=1, eos_id=-1,
                          sampling=sampling, priority=priority,
                          on_token=on_token)
        if not req.rejected:
            req.capture_kv = True
        return req

    def submit_handoff(self, handoff: KVHandoff, max_new_tokens: int = 16,
                       eos_id: Union[int, Sequence[int]] = -1,
                       sampling: Optional[SamplingParams] = None,
                       priority: int = 0,
                       on_token: Optional[Callable] = None) -> GenRequest:
        """Queue a prefilled request on a *decode worker*: ``handoff`` came
        from a peer engine's ``submit_prefill`` on the same ``SharedKVPool``,
        so the prompt's KV blocks attach to a slot with zero recompute and
        decoding resumes from the already-sampled first token.

        Ownership: an ACCEPTED request takes the handoff's block references
        (released when the request finishes or is cancelled). A REJECTED
        submission leaves ownership with the caller — the router re-
        dispatches the same handoff to another worker or releases it."""
        if not self.paged:
            raise ValueError("submit_handoff requires a paged engine")
        if handoff.consumed:
            raise ValueError("handoff already consumed or released")
        req = GenRequest(self._next_rid, handoff.tokens, max_new_tokens,
                         None, eos_id, out_tokens=[],
                         # repro: allow-wallclock -- TTFT/e2e gates measure real compute
                         submitted_at=time.perf_counter(),
                         sampling=sampling or SamplingParams(),
                         priority=priority, on_token=on_token)
        self._next_rid += 1
        self.all_requests.append(req)
        if self.max_queue_depth and self.queue_depth >= self.max_queue_depth:
            req.status = "rejected"
            self.rejected_total += 1
            return req
        total = req.prompt_len + max_new_tokens
        if (total > self.max_len
                or self.kv.blocks_for_tokens(total) + 1
                > self.kv.alloc.usable_blocks
                # KV pressure: the shared pool cannot supply even one block
                # of decode headroom right now — reject instead of queueing
                # work this worker cannot start (the router re-dispatches)
                or self.kv.alloc.available() < 1):
            req.status = "rejected"
            self.rejected_total += 1
            return req
        self.prompt_tokens_submitted += req.prompt_len
        req._handoff = handoff
        # the prefill worker already sampled the first token: record it so
        # streaming callbacks and EOS/budget checks see it exactly once
        req.status = "queued"
        self._record(req, handoff.first_token)
        if req.done:
            # max_new_tokens == 1 or the first token IS the EOS: nothing
            # left to decode — consume the handoff without taking a slot
            req._handoff = None
            handoff.release(self.kv.alloc)
            return req
        heapq.heappush(self._pending, (-priority, req.rid, req))
        return req

    def cancel(self, req: GenRequest) -> bool:
        """Withdraw an unfinished request. Queued entries are marked and
        lazily dropped from the heap; active ones release their slot (and
        blocks, in paged mode). A queued handoff request must also release
        the handoff blocks the engine took ownership of at submit — leaving
        them retained would leak pool blocks on every router-side timeout
        (the refcount-conservation property test pins this)."""
        if req.done or req.status in ("rejected", "cancelled"):
            return False
        if req._handoff is not None:
            req._handoff.release(self.kv.alloc)
            req._handoff = None
        req.status = "cancelled"
        slot = next((i for i, r in enumerate(self.active) if r is req), None)
        if slot is not None:
            self._release(slot)      # capture_kv guard: req.done is False
        self.cancelled_total += 1
        return True

    # ---------------------------------------------------------------- #
    def _admit(self) -> None:
        """Prefill the first chunk of pending requests into free slots."""
        with TraceAnnotation("serving.admit"):
            for slot in range(self.n_slots):
                if self.active[slot] is not None:
                    continue
                while (self._pending
                       and self._pending[0][2].status == "cancelled"):
                    heapq.heappop(self._pending)  # lazily drop cancellations
                if not self._pending:
                    continue
                if self.paged:
                    if not self._admit_paged(slot):
                        break    # pool cannot take the head request yet
                else:
                    _, _, req = heapq.heappop(self._pending)
                    self._admit_dense(slot, req)

    def _pad_tokens(self, batch: dict, cfg: ModelConfig, total: int) -> dict:
        """Bucket-pad the token axis so every prompt length in a power-of-
        two bucket reuses ONE compiled prefill. ``total`` counts frontend
        tokens; the result plus frontends never exceeds the cache
        (``_pad_len``). No-op for archs where pad tokens are not inert
        (MoE capacity, SSM state — see ``bucketed_prefill_ok``)."""
        if not bucketed_prefill_ok(cfg):
            return batch
        tb = min(pow2_bucket(total), self._pad_len) - cfg.n_frontend_tokens
        t = batch["tokens"]
        if t.shape[1] < tb:
            batch = dict(batch)
            batch["tokens"] = jnp.pad(t, ((0, 0), (0, tb - t.shape[1])))
        return batch

    def _admit_dense(self, slot: int, req: GenRequest) -> None:
        self._stamp_admitted(req)
        s = req.prompt_len
        chunk = min(self.prefill_chunk, s) if self.prefill_chunk else s
        with TraceAnnotation("serving.prefill", rid=req.rid, tokens=chunk):
            batch = {"tokens": req.tokens[:, :chunk]}
            if req.frontend_embeds is not None:
                # frontend embeds are prepended, so they ride the first chunk
                batch["frontend_embeds"] = req.frontend_embeds
            n_valid = chunk + self.cfg.n_frontend_tokens
            batch = self._pad_tokens(batch, self.cfg, n_valid)
            last, single_cache = self._prefill(self.params, batch,
                                               jnp.int32(n_valid))
        self.cache = _tree_insert(self.cache, single_cache, slot)
        self.positions = self.positions.at[slot].set(
            chunk + self.cfg.n_frontend_tokens)
        req.n_consumed = chunk
        self.prefill_tokens += chunk
        self.prompt_tokens_computed += chunk
        self.active[slot] = req
        if self.spec is not None:
            self._admit_draft(slot, req)
        if chunk == s:
            # whole prompt in cache: prefill logits give the first token
            nxt = sample(last[0, -1], req.sampling, 0)
            req.status = "decode"
            self._record(req, nxt)
            self._set_last(slot, nxt)
            if req.done:        # max_new_tokens=1 / EOS on the first token
                self._release(slot)
        else:
            # chunked: feed the rest of the prompt through the batched
            # decode step, one token per tick, alongside active decodes
            req.status = "prefill"
            self._set_last(slot, self._prompt_token(req, chunk))

    def _admit_paged(self, slot: int) -> bool:
        """Admission by free blocks (head of the priority queue only).

        Prefix-hit fast path: full prompt blocks found in the allocator's
        hash registry are attached with a refcount bump — no recompute —
        and the remaining tail rides the batched decode step. Cold prompts
        dense-prefill their full-block prefix (power-of-two padded) and
        scatter it into fresh blocks, registering hashes for reuse; the
        sub-block tail rides decode so hit and cold runs take the same
        numeric path for the tail.

        A *partial* hit whose uncached remainder is long (> 2 blocks) is
        deliberately demoted to the cold path: prefill cannot attend to
        cached blocks, so the remainder would otherwise crawl through
        decode one token per tick AND its blocks would never be
        registered. Recomputing the prefix once batch-prefills everything
        and registers the longer chain, so the next such request hits
        fully. Returns False (head stays queued) when the pool cannot
        supply the blocks."""
        kv = self.kv
        bs = kv.block_size
        nf = self.cfg.n_frontend_tokens
        req = self._pending[0][2]
        if req._handoff is not None:
            return self._admit_handoff(slot, req)
        tokens = req.feed_tokens
        s = tokens.shape[1]
        hashing = req.frontend_embeds is None and nf == 0
        n_hit = cached_hits = 0
        hashes: List[int] = []
        if hashing:
            if req._block_hashes is None:      # one host sync per admission
                self.host_syncs += 1
                req._block_hashes = hash_prompt_blocks(tokens[0].tolist(), bs)
            hashes = req._block_hashes
            # non-mutating probe: size the hit chain without touching
            # refcounts, LRU order, or allocator stats — a failed admission
            # must leave the allocator byte-identical
            for h in hashes[:(s - 1) // bs]:   # always recompute >= 1 token
                bid = kv.alloc.peek(h)
                if bid is None:
                    break
                n_hit += 1
                if kv.alloc.refcount(bid) == 0:
                    cached_hits += 1           # revival consumes a cached slot
            if n_hit and s - n_hit * bs > 2 * bs:
                # partial hit with a long uncached remainder: demote to the
                # cold path (one batched prefill + registration of the full
                # chain) instead of crawling the remainder through decode
                n_hit = cached_hits = 0
        hit = n_hit * bs
        if hit:
            chunk = 0                          # tail rides decode from `hit`
            cache_tokens = hit
        else:
            chunk = ((s - 1) // bs) * bs or s  # full-block prefix (or tiny)
            cache_tokens = nf + chunk
        needed = kv.blocks_for_tokens(cache_tokens) - n_hit
        if kv.alloc.available() - cached_hits < needed + 1:  # +1: decode block
            return False
        heapq.heappop(self._pending)
        self._stamp_admitted(req)
        for h in hashes[:n_hit]:
            kv.attach(slot, kv.alloc.lookup(h))
        req.prefix_hit += hit
        self.prefix_hit_tokens += hit
        if chunk:
            with TraceAnnotation("serving.prefill", rid=req.rid,
                                 tokens=chunk):
                batch = {"tokens": tokens[:, :chunk]}
                if req.frontend_embeds is not None:
                    batch["frontend_embeds"] = req.frontend_embeds
                # allocate the prompt's blocks up front (the admission
                # check above guarantees availability), then scatter K/V
                # straight into the pools inside the traced prefill — the
                # dense single-request cache never materializes
                while (len(kv.slot_blocks[slot])
                       < kv.blocks_for_tokens(cache_tokens)):
                    kv.grow(slot)
                batch = self._pad_tokens(batch, self.cfg, cache_tokens)
                last, kv.pools = self._prefill_paged(
                    self.params, kv.pools, batch, jnp.int32(cache_tokens),
                    kv.tables[slot:slot + 1])
            self.cache = kv.pools
            if hashing:
                for i in range(chunk // bs):
                    kv.alloc.register(kv.slot_blocks[slot][i], hashes[i])
            self.prefill_tokens += chunk
            # resume feeds append generated tokens; only the true prompt
            # portion counts as prompt recompute
            self.prompt_tokens_computed += min(chunk, req.prompt_len)
        else:
            last = None
        self.positions = self.positions.at[slot].set(cache_tokens)
        req.cache_pos = cache_tokens
        req.n_consumed = hit or chunk
        self.active[slot] = req
        if self.spec is not None:
            self._admit_draft(slot, req)
        if req.n_consumed == s:
            # whole feed in cache (tiny cold prompt): prefill logits give
            # the next token — or the pre-preemption token on resume
            if req._resume_last is not None:
                self._set_last(slot, req._resume_last)
                req._resume_last = None
                req.status = "decode"
            else:
                nxt = sample(last[0, -1], req.sampling, 0)
                req.status = "decode"
                self._record(req, nxt)
                self._set_last(slot, nxt)
                if req.done:    # max_new_tokens=1 / EOS on the first token
                    self._release(slot)
        else:
            req.status = "prefill"
            self._set_last(slot, self._prompt_token(req, req.n_consumed))
        return True

    def _admit_handoff(self, slot: int, req: GenRequest) -> bool:
        """Resume-style admission of a prefilled handoff: attach the peer
        engine's blocks to this slot's table (the handoff's references
        transfer — no recompute, no refcount change) and decode from the
        first token the prefill worker sampled. Requires one available
        block of decode headroom so the very next ``_ensure_blocks`` cannot
        immediately preempt the request we just admitted."""
        kv = self.kv
        if kv.alloc.available() < 1:
            return False
        heapq.heappop(self._pending)
        self._stamp_admitted(req)
        h = req._handoff
        req._handoff = None
        # ownership was taken at submit; a handoff consumed while queued
        # means a caller double-submitted it — corrupt refcounts ahead
        assert not h.consumed, "handoff consumed while queued"
        h.consumed = True
        kv.import_blocks(slot, h.block_ids)
        self.positions = self.positions.at[slot].set(h.cache_pos)
        req.cache_pos = h.cache_pos
        req.n_consumed = req.prompt_len
        req.prefix_hit += h.cache_pos        # served from the pool, not
        self.prefix_hit_tokens += h.cache_pos  # recomputed by this engine
        self.active[slot] = req
        if self.spec is not None:
            self._admit_draft(slot, req)
        req.status = "decode"
        self._set_last(slot, h.first_token)
        return True

    def _capture_handoff(self, slot: int, req: GenRequest) -> KVHandoff:
        """Export a finished prefill request's blocks for decode handoff.
        Registers every FULL prompt block under the prompt's hash chain
        (the cold prefill path registered only the pre-tail chain; the last
        full block may have been filled by decode-tail ticks), then retains
        each block so they all survive this slot's release."""
        kv = self.kv
        self.host_syncs += 1
        toks = req.tokens[0].tolist()
        hashes = (req._block_hashes
                  if req._block_hashes is not None
                  else hash_prompt_blocks(toks, kv.block_size))
        for i, h in enumerate(hashes):
            kv.alloc.register(kv.slot_blocks[slot][i], h)
        return KVHandoff(tokens=req.tokens,
                         first_token=req.out_tokens[0],
                         block_ids=kv.export_blocks(slot),
                         cache_pos=req.cache_pos,
                         block_hashes=tuple(hashes))

    def _release(self, slot: int) -> None:
        """Free a slot whose request just finished (blocks drop in paged
        mode). Admission must call this too: a done request left in
        ``active`` would be stepped again and emit a bogus extra token."""
        req = self.active[slot]
        if (req is not None and req.capture_kv and req.done and self.paged
                and req.kv_handoff is None):
            req.kv_handoff = self._capture_handoff(slot, req)
        self.active[slot] = None
        self.positions = self.positions.at[slot].set(0)
        if self.paged:
            self.kv.release_slot(slot)

    def _admit_draft(self, slot: int, req: GenRequest) -> None:
        """Prefill the draft's dense cache with the request's whole feed.
        The draft has no prefix cache: it re-prefills prompt (+ generated
        tokens on a preemption resume) even when the target got a
        prefix hit — draft KV sharing is a ROADMAP follow-up."""
        req._spec_pending = None
        dcfg = self.draft_cfg
        n_valid = req.feed_len + dcfg.n_frontend_tokens
        _, single = self._draft_prefill(
            self.draft_params,
            self._pad_tokens({"tokens": req.feed_tokens}, dcfg, n_valid),
            jnp.int32(n_valid))
        self.draft_cache = _tree_insert(self.draft_cache, single, slot)
        self.draft_positions = self.draft_positions.at[slot].set(req.feed_len)

    # ---------------------------------------------------------------- #
    def _pick_victim(self) -> Optional[int]:
        """Slot to preempt under block exhaustion: lowest priority first,
        youngest (highest rid) within a priority level."""
        best, best_key = None, None
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            key = (req.priority, -req.rid)
            if best_key is None or key < best_key:
                best, best_key = slot, key
        return best

    def _preempt(self, slot: int) -> None:
        """Evict ``slot`` back to the queue, freeing its blocks. On
        re-admission it re-prefills prompt + generated-so-far and resumes
        decoding from the pre-preemption token — token-identical to an
        uninterrupted run (greedy is exact argmax; sampling is seeded per
        token index)."""
        req = self.active[slot]
        gen = req.out_tokens or []
        if gen:
            if len(gen) > 1:
                tail = jnp.asarray(gen[:-1], req.tokens.dtype)[None]
                req._admit_tokens = jnp.concatenate([req.tokens, tail], axis=1)
            else:
                req._admit_tokens = req.tokens
            req._resume_last = gen[-1]
        else:
            req._admit_tokens = None
            req._resume_last = None
        req._block_hashes = None               # feed changed: re-hash on admit
        self.kv.release_slot(slot)
        self.active[slot] = None
        self.positions = self.positions.at[slot].set(0)
        req.status = "queued"
        req.n_consumed = 0
        req.cache_pos = 0
        req.preemptions += 1
        self.preempted_total += 1
        heapq.heappush(self._pending, (-req.priority, req.rid, req))

    def _ensure_blocks(self) -> None:
        """Grow every active slot's table to cover its next write position
        (the whole k+1 verify span for spec engines), preempting victims
        when the pool is exhausted."""
        kv = self.kv
        bs = kv.block_size
        span = self._spec_m if self.spec is not None else 1
        with TraceAnnotation("serving.ensure_blocks"):
            for slot in range(self.n_slots):
                req = self.active[slot]
                if req is None:
                    continue
                while ((req.cache_pos + span - 1) // bs
                       >= len(kv.slot_blocks[slot])):
                    if kv.grow(slot):
                        continue
                    victim = self._pick_victim()
                    if victim is None:  # unreachable: submit() guards size
                        raise MemoryError("paged KV pool exhausted with no "
                                          "preemptible request")
                    self._preempt(victim)
                    if victim == slot:
                        break           # this slot itself was evicted

    def _prompt_token(self, req: GenRequest, i: int):
        return req.feed_tokens[0, i]

    def _set_last(self, slot: int, token) -> None:
        self.last_tokens = self.last_tokens.at[slot].set(
            jnp.asarray(token, jnp.int32).reshape(self.last_tokens.shape[1:]))

    def _stamp_admitted(self, req: GenRequest) -> None:
        if not req.admitted_at:        # a resume keeps the first admission
            # repro: allow-wallclock -- queueing interval vs submitted_at
            req.admitted_at = time.perf_counter()

    def _record(self, req: GenRequest, token) -> None:
        if isinstance(token, jax.Array):
            self.host_syncs += 1
            tok = token.tolist()
        else:
            tok = token
        if not req.out_tokens:
            # repro: allow-wallclock -- TTFT interval vs submitted_at
            req.first_token_at = time.perf_counter()
        req.out_tokens.append(tok)
        if req.on_token is not None:
            req.on_token(req, tok)
        if len(req.out_tokens) >= req.max_new_tokens or _hits_eos(tok, req.eos_id):
            req.done = True
            req.status = "done"
            # repro: allow-wallclock -- e2e-latency interval vs submitted_at
            req.finished_at = time.perf_counter()

    # ---------------------------------------------------------------- #
    # Speculative decoding step (spec engines)
    # ---------------------------------------------------------------- #
    def _draft_phase(self, decode_slots: List[int]
                     ) -> Tuple[Dict[int, List[int]],
                                Dict[int, List[Any]], Dict[int, List[int]]]:
        """k batched draft decode steps. Each decode-status slot's feed is
        its pending tokens (committed tokens the draft cache still lacks)
        followed by the draft's own proposals; idle/prefill slots feed a
        zero token at the scratch position. Returns (proposals, draft
        probability rows for sampled slots, pending per slot)."""
        proposals: Dict[int, List[int]] = {s: [] for s in decode_slots}
        dprobs: Dict[int, List[Any]] = {s: [] for s in decode_slots}
        pend: Dict[int, List[int]] = {}
        n0: Dict[int, int] = {}
        for s in decode_slots:
            req = self.active[s]
            pend[s] = list(req._spec_pending or [req.out_tokens[-1]])
            n0[s] = len(req.out_tokens)
        in_decode = jnp.asarray(
            [r is not None and r.status == "decode" for r in self.active])
        base_pos = jnp.where(in_decode, self.draft_positions,
                             self._draft_trash)
        for i in range(self.spec_k):
            feed = [0] * self.n_slots
            for s in decode_slots:
                j = i - len(pend[s])
                feed[s] = int(pend[s][i] if j < 0 else proposals[s][j])
            toks = jnp.asarray(feed, jnp.int32).reshape(self.n_slots, 1)
            logits, self.draft_cache = self._draft_decode(
                self.draft_params, self.draft_cache, toks, base_pos + i)
            last = logits[:, -1]
            batch_argmax = None
            for s in decode_slots:
                j = i - len(pend[s]) + 1     # proposal produced this round
                if j < 0:
                    continue                 # still catching up on pending
                req = self.active[s]
                if req.sampling.is_greedy:
                    if batch_argmax is None:
                        self.host_syncs += 1
                        batch_argmax = jnp.argmax(last, axis=-1).tolist()
                    proposals[s].append(int(batch_argmax[s]))
                else:
                    self.host_syncs += 1       # the drawn token
                    tok, probs = draft_propose(last[s], req.sampling,
                                               n0[s] + j)
                    proposals[s].append(tok)
                    dprobs[s].append(probs)
        return proposals, dprobs, pend

    def _step_spec(self) -> int:
        """Spec engine step: admit -> draft k proposals -> one multi-token
        verify -> per-slot accept/commit with rollback. Prompt-feeding
        slots ride the same verify pass, consuming up to k+1 feed tokens.
        Returns #occupied (same contract as ``step``)."""
        self._admit()
        if self.paged:
            self._ensure_blocks()            # covers the whole verify span
        active_idx = [s for s in range(self.n_slots)
                      if self.active[s] is not None]
        if not active_idx:
            return 0
        m = self._spec_m
        decode_slots = [s for s in active_idx
                        if self.active[s].status == "decode"]
        proposals, dprobs, _ = (self._draft_phase(decode_slots)
                                if decode_slots else ({}, {}, {}))
        # candidate matrix [B, m]: [last committed, draft proposals...] for
        # decode slots, the next feed tokens for prompt-feeding slots,
        # zero-padded (pad writes are stale-by-position and overwritten)
        cand = [[0] * m for _ in range(self.n_slots)]
        t_feed: Dict[int, int] = {}
        for s in active_idx:
            req = self.active[s]
            if req.status == "decode":
                row = [int(req.out_tokens[-1])] + proposals[s]
            else:
                t_f = min(m, req.feed_len - req.n_consumed)
                t_feed[s] = t_f
                self.host_syncs += 1
                row = [int(t) for t in
                       req.feed_tokens[0, req.n_consumed:
                                       req.n_consumed + t_f].tolist()]
            cand[s][:len(row)] = row
        cand_arr = jnp.asarray(cand, jnp.int32)
        with TraceAnnotation("serving.decode", live=len(active_idx)):
            if self.paged:
                logits, self.kv.pools = self._verify_paged(
                    self.params, self.kv.pools, cand_arr, self.positions,
                    self.kv.tables)
                self.cache = self.kv.pools
            else:
                logits, self.cache = self._verify(self.params, self.cache,
                                                  cand_arr, self.positions)
        with TraceAnnotation("serving.harvest"):
            return self._harvest_spec(active_idx, proposals, dprobs, t_feed,
                                      logits)

    def _harvest_spec(self, active_idx: List[int],
                      proposals: Dict[int, List[int]],
                      dprobs: Dict[int, List[Any]], t_feed: Dict[int, int],
                      logits) -> int:
        """Accept and commit each slot's share of one verify pass, then
        roll back positions and blocks past what was committed."""
        self.steps += 1
        tgt_argmax = None
        pos_delta = [0] * self.n_slots
        n_occupied = 0
        for s in active_idx:
            req = self.active[s]
            if req.status != "decode":
                n_occupied += self._commit_feed(s, req, t_feed[s], logits)
                pos_delta[s] = t_feed[s]
            else:
                k_s = len(proposals[s])
                if req.sampling.is_greedy:
                    if tgt_argmax is None:
                        self.host_syncs += 1
                        tgt_argmax = jnp.argmax(logits, axis=-1).tolist()
                    n_acc, toks = greedy_accept(proposals[s],
                                                tgt_argmax[s][:k_s + 1])
                else:
                    n_acc, toks = rejection_sample(
                        proposals[s], dprobs[s], logits[s], req.sampling,
                        len(req.out_tokens))
                    # two reads (draw, ratio) per draft token judged, one
                    # for the correction or bonus token
                    self.host_syncs += 2 * min(n_acc + 1, k_s) + 1
                occupied, c = self._commit_spec(s, req, n_acc, k_s, toks)
                n_occupied += occupied
                pos_delta[s] = c
                req.cache_pos += c
            if req.done:
                self._release(s)
                pos_delta[s] = 0
        self.positions = self.positions + jnp.asarray(pos_delta, jnp.int32)
        if self.paged:
            # rollback: drop tail blocks that only ever held rejected
            # verify writes (or pad garbage) — pool accounting must not
            # carry dead speculation between steps
            for s in active_idx:
                req = self.active[s]
                if req is not None:
                    self.kv.truncate(
                        s, self.kv.blocks_for_tokens(req.cache_pos))
        return n_occupied

    def _commit_feed(self, slot: int, req: GenRequest, t_f: int,
                     logits) -> int:
        """Advance a prompt-feeding slot by the ``t_f`` feed tokens the
        verify pass just wrote; on completion emit the first new token
        (or swap in the pre-preemption resume token)."""
        start = req.n_consumed
        req.n_consumed += t_f
        req.cache_pos += t_f
        self.prompt_tokens_computed += (min(req.n_consumed, req.prompt_len)
                                        - min(start, req.prompt_len))
        if req.n_consumed < req.feed_len:
            self._set_last(slot, self._prompt_token(req, req.n_consumed))
            return 1
        req.status = "decode"
        if req._resume_last is not None:
            self._set_last(slot, req._resume_last)
            req._resume_last = None
            return 1
        nxt = sample(logits[slot, t_f - 1], req.sampling,
                     len(req.out_tokens))
        self._record(req, nxt)
        self._set_last(slot, nxt)
        return 0 if req.done else 1

    def _commit_spec(self, slot: int, req: GenRequest, n_acc: int,
                     k_s: int, toks: List[int]) -> Tuple[int, int]:
        """Commit one verify round's tokens (stopping at EOS/budget) and
        update acceptance stats and the draft-side bookkeeping. Returns
        (still_occupied, tokens_committed)."""
        c = 0
        for t in toks:
            self._record(req, int(t))
            c += 1
            if req.done:
                break
        self.spec_events += 1
        self.spec_committed += c
        self.draft_proposed += k_s
        accepted = min(n_acc, c)
        self.draft_accepted += accepted
        req.spec_events += 1
        req.spec_accepted += accepted
        if req.done:
            req._spec_pending = None
            return 0, c
        if c == k_s + 1 and n_acc == k_s:
            # bonus round: the draft never consumed its own last proposal,
            # so the next draft phase must feed it before the bonus token
            req._spec_pending = [toks[c - 2], toks[c - 1]]
        else:
            req._spec_pending = [toks[c - 1]]
        self._set_last(slot, toks[c - 1])
        total = req.prompt_len + len(req.out_tokens)
        self.draft_positions = self.draft_positions.at[slot].set(
            total - len(req._spec_pending))
        return 1, c

    # ---------------------------------------------------------------- #
    def step(self) -> int:
        """Admit -> one batched decode step -> harvest. Returns #occupied."""
        with TraceAnnotation("serving.step", step=self.steps):
            if self.spec is not None:
                return self._step_spec()
            return self._step()

    def _step(self) -> int:
        self._admit()
        if self.paged:
            self._ensure_blocks()                # may preempt under pressure
        live = [r for r in self.active if r is not None]
        if not live:
            return 0
        # args: occupied slots, the cache positions they attend, and (paged)
        # the blocks the decode kernel computes over all slots
        args = {"live": len(live), "context": sum(r.cache_pos + 1 for r in live)}
        if self.paged:
            args["blocks"] = self._walked_blocks()
        with TraceAnnotation("serving.decode", **args):
            if self.paged:
                logits, self.kv.pools = self._decode_paged(
                    self.params, self.kv.pools, self.last_tokens,
                    self.positions, self.kv.tables)
                self.cache = self.kv.pools
            else:
                logits, self.cache = self._decode(
                    self.params, self.cache, self.last_tokens,
                    self.positions)
        with TraceAnnotation("serving.harvest"):
            return self._harvest(logits)

    def _walked_blocks(self) -> int:
        """Blocks the paged decode kernel computes this step, from the host
        mirror: each slot walks up to its last live block, an idle slot
        (position 0) its block 0 alone."""
        bs, m = self.kv.block_size, self.kv.max_blocks
        return sum(1 if r is None else min(r.cache_pos // bs, m - 1) + 1
                   for r in self.active)

    def _harvest(self, logits) -> int:
        """Emit each decode slot's next token, or feed a prompt slot its
        next prompt token; free the slots that finished."""
        # idle slots stay at position 0: the paged kernel's walk is bounded
        # by position, so an idle slot costs one block
        self.positions = self.positions + jnp.asarray(
            [r is not None for r in self.active], jnp.int32)
        last = logits[:, -1]                     # [B, V] or [B, K, V]
        # one batched argmax serves every greedy slot (the common case);
        # only non-greedy requests pay a per-slot sampling dispatch
        greedy = (jnp.argmax(last, axis=-1).astype(jnp.int32)
                  if any(r is not None and r.sampling.is_greedy
                         for r in self.active) else None)
        self.steps += 1
        n_occupied = 0
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            req.cache_pos += 1                   # host mirror of positions
            if req.n_consumed < req.feed_len:
                # this tick consumed one feed token (chunked-prefill tail,
                # prefix-hit tail, or preemption-resume replay)
                req.n_consumed += 1
                if req.n_consumed <= req.prompt_len:
                    # replayed generated tokens (resume) are not prompt work
                    self.prompt_tokens_computed += 1
                if req.n_consumed < req.feed_len:
                    self._set_last(slot, self._prompt_token(req, req.n_consumed))
                    n_occupied += 1
                    continue
                req.status = "decode"   # logits now predict the next token
                if req._resume_last is not None:
                    # resume: the "next token" was already generated before
                    # the preemption — feed it, don't re-record it
                    self._set_last(slot, req._resume_last)
                    req._resume_last = None
                    n_occupied += 1
                    continue
            nxt = (greedy[slot] if req.sampling.is_greedy
                   else sample(last[slot], req.sampling, len(req.out_tokens)))
            self._record(req, nxt)
            self._set_last(slot, nxt)
            if req.done:
                self._release(slot)              # slot frees mid-flight
            else:
                n_occupied += 1
        return n_occupied

    def run(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.has_work:
                break
            self.step()

    # ---------------------------------------------------------------- #
    def metrics(self, reqs: Optional[List[GenRequest]] = None
                ) -> Dict[str, float]:
        """Aggregate serving metrics over ``reqs`` (default: every request
        ever submitted). Always returns the full ``METRIC_KEYS`` set —
        zeroed where nothing finished — so JSON reports built on top have a
        stable schema."""
        if reqs is None:
            reqs = self.all_requests
        done = [r for r in reqs if r.done]
        m = dict.fromkeys(METRIC_KEYS, 0.0)
        m.update(
            completed=len(done),
            rejected=sum(1 for r in reqs if r.rejected),
            queued=self.queue_depth,
            active=sum(1 for r in self.active if r is not None),
            submitted=len(reqs),
            decode_steps=self.steps,
            generated_tokens=sum(len(r.out_tokens or []) for r in reqs),
            prefill_tokens=self.prefill_tokens,
            preempted=self.preempted_total,
            host_syncs=self.host_syncs,
            cancelled=sum(1 for r in reqs if r.status == "cancelled"),
            prefix_hit_tokens=self.prefix_hit_tokens,
            prompt_tokens_computed=self.prompt_tokens_computed,
            prefix_hit_rate=(self.prefix_hit_tokens
                             / self.prompt_tokens_submitted
                             if self.prompt_tokens_submitted else 0.0),
            kv_blocks_peak=(self.kv.alloc.stats.peak_in_use
                            if self.paged else 0),
            tp=self.tp,
            spec_events=self.spec_events,
            spec_draft_tokens=self.draft_proposed,
            spec_accepted_tokens=self.draft_accepted,
            acceptance_rate=(self.draft_accepted / self.draft_proposed
                             if self.draft_proposed else 0.0),
            accepted_tokens_per_step=(self.spec_committed / self.spec_events
                                      if self.spec_events else 0.0),
        )
        if not done:
            return m
        # peak cache HBM per concurrent request: dense reserves the whole
        # (n_slots, max_len) cache up front; paged holds only the blocks
        # actually touched (high-water mark), shared prefixes counted once
        if self.paged:
            kv_bytes = self.kv.kv_bytes_in_use(self.kv.alloc.stats.peak_in_use)
            shard_bytes = self.kv.kv_bytes_in_use_per_shard(
                self.kv.alloc.stats.peak_in_use)
        else:
            from repro.serving.kvcache import kv_shard_divisor

            # .nbytes on a sharded jax.Array reports the GLOBAL footprint —
            # divide explicitly for the per-chip share
            kv_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(self.cache))
            shard_bytes = kv_bytes // kv_shard_divisor(self.cfg, self.tp)
        m["kv_hbm_bytes_per_req"] = kv_bytes / self.n_slots
        m["kv_hbm_bytes_per_req_per_shard"] = shard_bytes / self.n_slots
        ttft = [r.first_token_at - r.submitted_at for r in done]
        total = [r.finished_at - r.submitted_at for r in done]
        toks = sum(len(r.out_tokens) for r in done)
        wall = max(r.finished_at for r in done) - min(r.submitted_at
                                                      for r in done)
        m.update(
            mean_ttft_s=sum(ttft) / len(ttft),
            p50_ttft_s=interpolated_percentile(ttft, 0.5),
            p90_ttft_s=interpolated_percentile(ttft, 0.9),
            p99_ttft_s=interpolated_percentile(ttft, 0.99),
            mean_latency_s=sum(total) / len(total),
            throughput_tok_s=toks / max(wall, 1e-9),
        )
        return m
