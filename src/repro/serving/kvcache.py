"""Paged KV-cache v2: block allocator + pooled block storage (tentpole).

The dense serving cache reserves ``(n_slots, max_len)`` KV slots up front,
so HBM scales with *worst-case* sequence length and admission is slot-count
based. This module replaces it with a vLLM-style paged subsystem sized for
the paper's edge budgets (Pi-4-class devices):

* ``BlockAllocator`` — host-side metadata for a pool of fixed-size token
  blocks: refcounted sharing (copy-on-write via ``ensure_writable``),
  hash-based prefix registry over full prompt blocks, and an LRU
  "cached-free" list so freed-but-registered blocks survive until memory
  pressure actually evicts them.
* ``PagedKVCache`` — the device-side pools (one ``[L, N, block_size, ...]``
  leaf per layer-stack cache leaf, mirroring ``repro.models.init_cache``)
  plus jnp block tables, the scatter that moves a dense batch-1 prefill
  cache into allocated blocks, and quantized block storage per
  ``cfg.kv_precision``: int8 payloads with per-(block, slot, head) scales,
  or nibble-packed int4 payloads with per-(block, slot, head, group)
  scales (``kernels.quantize.KV_GROUP`` head_dim elements per group).

Attention reads the pools through per-request block tables
(``repro.models.attention.gqa_decode_paged`` / ``mla_decode_paged``,
dispatched to the ``paged_decode`` / ``paged_qdecode`` backend primitives),
so two requests whose tables point at the same block share its KV bytes —
that is what turns the paper's weight-quantization story into a cache-memory
story: admission, sharing, and eviction all operate on 16-token blocks
instead of max-length slots.

Supported archs: attention-only stacks (GQA or MLA) with full attention
(``window == 0``) and a single codebook — sliding-window, SSM/hybrid and
multi-codebook models keep the dense compat path in the scheduler.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

#: table entries below 0 mean "no block allocated"; gathers clamp to the
#: reserved trash block 0 and mask by position validity.
NO_BLOCK = -1
#: block id 0 is reserved: padded scatter writes land there harmlessly and
#: clamped gathers of unallocated table entries read from it (masked out).
TRASH_BLOCK = 0


def paged_supported(cfg: ModelConfig) -> Optional[str]:
    """Why ``cfg`` cannot use the paged cache, or None if it can."""
    if cfg.arch_type not in ("dense", "moe"):
        return f"arch_type {cfg.arch_type!r} has non-attention caches"
    if cfg.window:
        return "sliding-window attention keeps the dense ring-buffer cache"
    if cfg.n_codebooks > 1:
        return "multi-codebook models keep the dense cache"
    return None


def bucketed_prefill_ok(cfg: ModelConfig) -> bool:
    """Whether prefill may pad *tokens* (not just the cache) to a bucket.

    Token-bucketed prefill feeds pad tokens through the backbone and slices
    logits at the true last position (``n_valid``), so every prompt length
    in a bucket shares ONE compiled prefill. Pad tokens are attention-masked
    but still occupy rows, which would pollute MoE expert-capacity routing
    and SSM recurrent state — those archs keep exact-length prefill.
    Sliding windows and multi-codebook models keep their bespoke paths too.
    """
    return (cfg.arch_type == "dense" and not cfg.window
            and cfg.n_codebooks <= 1)


def pow2_bucket(n: int, floor: int = 16) -> int:
    """Next power-of-two >= n (min ``floor``) — the shared padding bucket
    used by prefill so distinct prompt lengths reuse compiled shapes."""
    n = max(int(n), 1)
    return max(floor, 1 << (n - 1).bit_length())


def hash_prompt_blocks(tokens: Sequence[int], block_size: int,
                       salt: Any = None) -> List[int]:
    """Chained content hashes, one per FULL block of ``tokens``: block i's
    hash covers tokens[0 : (i+1)*block_size], so equal hashes imply equal
    prefixes (up to hash collisions over Python's tuple hash — acceptable
    for a cache key; a collision yields a wrong *reuse*, guarded by the
    chain covering the entire prefix)."""
    out: List[int] = []
    h = hash(("kv-prefix", salt))
    for i in range(len(tokens) // block_size):
        h = hash((h, tuple(tokens[i * block_size:(i + 1) * block_size])))
        out.append(h)
    return out


@dataclasses.dataclass
class AllocatorStats:
    evictions: int = 0            # cached blocks dropped for reuse
    cow_copies: int = 0           # copy-on-write block duplications
    peak_in_use: int = 0          # high-water mark of referenced blocks

    def reset(self) -> None:
        self.evictions = self.cow_copies = 0
        self.peak_in_use = 0


class BlockAllocator:
    """Host-side metadata for ``n_blocks`` fixed-size KV blocks.

    Invariants:
      * a block is in exactly one of: free list, cached LRU (refcount 0 but
        hash-registered), or in use (refcount >= 1);
      * ``lookup`` revives cached blocks (refcount 0 -> 1);
      * eviction only touches the cached LRU — referenced blocks are never
        reclaimed (callers preempt requests to create free blocks).
    """

    def __init__(self, n_blocks: int, block_size: int):
        if n_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved)")
        self.n_blocks = n_blocks
        self.block_size = block_size
        # block 0 is the reserved trash block — never handed out
        self._free: deque = deque(range(1, n_blocks))
        self._ref: List[int] = [0] * n_blocks
        self._hash: List[Optional[int]] = [None] * n_blocks
        self._by_hash: Dict[int, int] = {}            # live hash -> block
        self._cached: "OrderedDict[int, int]" = OrderedDict()  # hash -> block (LRU)
        self.stats = AllocatorStats()

    # ------------------------------------------------------------- #
    @property
    def usable_blocks(self) -> int:
        return self.n_blocks - 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_cached(self) -> int:
        return len(self._cached)

    @property
    def in_use(self) -> int:
        return self.usable_blocks - self.n_free - self.n_cached

    def available(self) -> int:
        """Blocks obtainable without preempting anyone (free + evictable)."""
        return self.n_free + self.n_cached

    def refcount(self, bid: int) -> int:
        return self._ref[bid]

    # ------------------------------------------------------------- #
    def alloc(self) -> Optional[int]:
        """One fresh block (refcount 1, no hash), or None when exhausted.
        Prefers truly-free blocks; otherwise evicts the LRU cached block."""
        if self._free:
            bid = self._free.popleft()
        elif self._cached:
            h, bid = self._cached.popitem(last=False)      # LRU eviction
            del self._by_hash[h]
            self._hash[bid] = None
            self.stats.evictions += 1
        else:
            return None
        self._ref[bid] = 1
        self.stats.peak_in_use = max(self.stats.peak_in_use, self.in_use)
        return bid

    def retain(self, bid: int) -> int:
        """refcount++ (sharing an existing block)."""
        assert self._ref[bid] >= 1, f"retain of unreferenced block {bid}"
        self._ref[bid] += 1
        return bid

    def free(self, bid: int) -> None:
        """refcount--; at zero the block returns to the cached LRU when it
        carries a registered hash (reusable prefix), else to the free list."""
        assert self._ref[bid] >= 1, f"double free of block {bid}"
        self._ref[bid] -= 1
        if self._ref[bid]:
            return
        h = self._hash[bid]
        if h is not None and self._by_hash.get(h) == bid:
            self._cached[h] = bid
        else:
            if h is not None:
                self._hash[bid] = None
            self._free.append(bid)

    # ------------------------------------------------------------- #
    def register(self, bid: int, h: int) -> None:
        """Publish ``bid`` as the cached block for prefix hash ``h``. An
        existing mapping for ``h`` wins (first writer keeps serving the
        prefix). A block carries at most ONE hash: re-registering a block
        under a new hash retires its old mapping — otherwise the stale
        ``_by_hash`` entry would keep serving the old prefix from a block
        whose content no longer matches it (found by the property-based
        allocator test)."""
        old = self._hash[bid]
        if old is not None and old != h and self._by_hash.get(old) == bid:
            # the block's content now corresponds to ``h``: its old mapping
            # must retire even when ``h`` itself is already served by
            # another block (early return below) — otherwise lookup(old)
            # would keep attaching content that no longer matches
            del self._by_hash[old]
            self._hash[bid] = None
        if h in self._by_hash:
            return
        self._by_hash[h] = bid
        self._hash[bid] = h

    def peek(self, h: int) -> Optional[int]:
        """Non-mutating prefix probe: the block registered for ``h`` (no
        refcount bump, no LRU reordering, no stats). Admission uses this to
        size a request before committing — a failed probe must leave the
        allocator byte-identical."""
        return self._by_hash.get(h)

    def lookup(self, h: int) -> Optional[int]:
        """Prefix hit: returns the block for ``h`` with refcount bumped
        (reviving it from the cached LRU if needed), else None."""
        bid = self._by_hash.get(h)
        if bid is None:
            return None
        if self._ref[bid] == 0:
            self._cached.pop(h, None)                      # revive
            self._ref[bid] = 1
            self.stats.peak_in_use = max(self.stats.peak_in_use, self.in_use)
        else:
            self._ref[bid] += 1
        return bid

    def ensure_writable(self, bid: int) -> Tuple[int, bool]:
        """Copy-on-write: a block shared with other tables (refcount > 1) or
        published in the prefix registry must not be mutated in place.
        Returns ``(writable_bid, needs_copy)`` — when ``needs_copy`` the
        caller must copy the pool contents from ``bid`` to the new id.

        The scheduler's write discipline (only FULL blocks are shared, and
        decode always writes into freshly grown private blocks) never needs
        this today; it is the safety valve for partial-block sharing
        schemes and is pinned by the allocator/pool API tests."""
        if self._ref[bid] == 1 and self._hash[bid] is None:
            return bid, False
        new = self.alloc()
        if new is None:
            raise MemoryError("no block available for copy-on-write")
        self.free(bid)
        self.stats.cow_copies += 1
        return new, True

    def reset(self) -> None:
        """Drop every table, hash and cached block (engine warmup uses this
        so measurement runs start truly cold)."""
        self._free = deque(range(1, self.n_blocks))
        self._ref = [0] * self.n_blocks
        self._hash = [None] * self.n_blocks
        self._by_hash.clear()
        self._cached.clear()
        self.stats.reset()


# ------------------------------------------------------------------ #
# Device-side pools
# ------------------------------------------------------------------ #
def init_paged_pools(cfg: ModelConfig, n_blocks: int,
                     block_size: int) -> Dict[str, Any]:
    """Block pools mirroring ``repro.models.init_cache`` structure: every
    dense leaf ``[L, B, S, ...]`` becomes ``[L, N, block_size, ...]`` — one
    shared pool instead of per-slot reservations. int8 mode stores int8
    payloads plus per-(block, slot, head) f32 scales, exactly the layout
    ``paged_qdecode`` consumes; int4 mode stores nibble-packed ``hd // 2``
    payloads plus per-(block, slot, head, group) scales for
    ``paged_q4decode``."""
    why = paged_supported(cfg)
    if why is not None:
        raise ValueError(f"paged KV cache unsupported for {cfg.name}: {why}")
    dt = cfg.activation_dtype
    hd = cfg.resolved_head_dim
    bs = block_size

    def kv(n):
        prec = cfg.kv_precision
        if prec == "int4":
            from repro.kernels.quantize import kv_group_size

            ng = hd // kv_group_size(hd)
            return (jnp.zeros((n, n_blocks, bs, cfg.n_kv_heads, hd // 2),
                              jnp.int8),
                    jnp.zeros((n, n_blocks, bs, cfg.n_kv_heads, ng),
                              jnp.float16),
                    jnp.zeros((n, n_blocks, bs, cfg.n_kv_heads, hd // 2),
                              jnp.int8),
                    jnp.zeros((n, n_blocks, bs, cfg.n_kv_heads, ng),
                              jnp.float16))
        if prec == "int8":
            return (jnp.zeros((n, n_blocks, bs, cfg.n_kv_heads, hd), jnp.int8),
                    jnp.zeros((n, n_blocks, bs, cfg.n_kv_heads), jnp.float32),
                    jnp.zeros((n, n_blocks, bs, cfg.n_kv_heads, hd), jnp.int8),
                    jnp.zeros((n, n_blocks, bs, cfg.n_kv_heads), jnp.float32))
        return (jnp.zeros((n, n_blocks, bs, cfg.n_kv_heads, hd), dt),
                jnp.zeros((n, n_blocks, bs, cfg.n_kv_heads, hd), dt))

    def mla(n):
        return (jnp.zeros((n, n_blocks, bs, cfg.kv_lora_rank), dt),
                jnp.zeros((n, n_blocks, bs, cfg.qk_rope_dim), dt))

    mk = mla if cfg.attention == "mla" else kv
    n_main = cfg.n_layers - cfg.n_dense_layers if cfg.n_experts else cfg.n_layers
    pools: Dict[str, Any] = {}
    if cfg.n_experts and cfg.n_dense_layers:
        pools["head_layers"] = mk(cfg.n_dense_layers)
    pools["layers"] = mk(n_main)
    return pools


def kv_pool_signature(cfg: ModelConfig, n_blocks: int,
                      block_size: int) -> Tuple:
    """Geometry + precision fingerprint of a block pool. Two engines may
    share one ``SharedKVPool`` only when their configs produce identical
    signatures — block ids are raw indices into the pool leaves, so any
    shape or dtype mismatch would read garbage, not raise."""
    return (cfg.attention, cfg.n_layers, cfg.n_dense_layers if cfg.n_experts
            else 0, cfg.n_kv_heads, cfg.resolved_head_dim, cfg.kv_lora_rank,
            cfg.qk_rope_dim, cfg.kv_precision, str(cfg.activation_dtype),
            n_blocks, block_size)


class SharedKVPool:
    """One allocator + one set of device pools shared by several engines.

    Disaggregated prefill/decode serving needs the *same* physical blocks
    visible from every engine: a prefill worker scatters a prompt's KV into
    pool blocks and a decode worker's block table then points at those ids
    with zero recompute. Each ``PagedKVCache`` built with ``shared=`` keeps
    its own slots/tables but delegates ``alloc`` and ``pools`` here, so the
    functional pool updates every engine performs (``kv.pools = new``)
    land in one place and are immediately visible to its peers.
    """

    def __init__(self, cfg: ModelConfig, n_blocks: int, block_size: int, *,
                 shards: int = 1, pool_sharding=None):
        self.cfg = cfg
        self.block_size = block_size
        self.shards = max(int(shards), 1)
        self.signature = kv_pool_signature(cfg, n_blocks, block_size)
        self.alloc = BlockAllocator(n_blocks, block_size)
        pools = init_paged_pools(cfg, n_blocks, block_size)
        self.pools = pools if pool_sharding is None else pool_sharding(pools)

    def reset(self) -> None:
        """Drop all allocator state. Only safe when every attached engine is
        idle — callers (router warmup) must release all slots first."""
        self.alloc.reset()


@dataclasses.dataclass
class KVHandoff:
    """Ownership token for a prompt's KV blocks, produced by a prefill
    worker and consumed by a decode worker sharing the same pool.

    The prefill engine retains every block before releasing its slot, so
    the blocks stay live (refcount >= 1) with the handoff as their owner.
    Full prompt blocks are also hash-registered, so even if the handoff is
    dropped the work survives as reusable prefix cache. Exactly one of
    ``consume``/``release`` must eventually run: ``consume`` transfers the
    refcounts into a decode slot's table, ``release`` drops them.
    """

    tokens: Any                      # [1, S] prompt (device or list)
    first_token: int                 # the one token the prefill step sampled
    block_ids: Tuple[int, ...]       # pool blocks, prompt order
    cache_pos: int                   # materialized positions (== prompt len)
    block_hashes: Tuple[int, ...]    # chained hashes of the full blocks
    consumed: bool = False

    def release(self, alloc: BlockAllocator) -> None:
        """Drop the handoff's ownership (request cancelled / rejected for
        good). Registered blocks fall back to the cached-LRU prefix tier;
        the partial tail block returns to the free list."""
        if self.consumed:
            return
        self.consumed = True
        for bid in self.block_ids:
            alloc.free(bid)


@jax.jit
def _scatter_leaf(pool, dense, ids):
    """pool [L,N,bs,...] <- dense [L,1,M*bs,...] at block ids [M]."""
    l, n, bs = pool.shape[:3]
    m = ids.shape[0]
    view = dense[:, 0, :m * bs].reshape((l, m, bs) + pool.shape[3:])
    return pool.at[:, ids].set(view.astype(pool.dtype))


@jax.jit
def _copy_block_leaf(pool, src, dst):
    return pool.at[:, dst].set(pool[:, src])


class PagedKVCache:
    """Pools + allocator + jnp block tables for ``n_slots`` decode slots.

    ``tables`` is ``[n_slots, max_blocks]`` int32 (NO_BLOCK where
    unallocated); the python-side ``slot_blocks`` lists are authoritative
    and the jnp array is rebuilt lazily (``tables`` property) so the hot
    decode loop never syncs device -> host."""

    def __init__(self, cfg: ModelConfig, n_slots: int, n_blocks: int,
                 block_size: int, max_blocks_per_seq: int, *,
                 shards: int = 1, pool_sharding=None,
                 shared: Optional[SharedKVPool] = None):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_blocks = max_blocks_per_seq
        # tensor-parallel serving: each of ``shards`` devices holds its
        # kv-head slice of every pool leaf. Block tables, the allocator,
        # and slot bookkeeping stay host-side and replicated — sharding
        # never changes block identity, only where a block's payload lives.
        if shared is not None:
            sig = kv_pool_signature(cfg, shared.alloc.n_blocks,
                                    shared.block_size)
            if sig != shared.signature:
                raise ValueError(
                    "engine config incompatible with the shared KV pool: "
                    f"{sig} != {shared.signature}")
            self.store = shared
            self.owns_store = False
        else:
            self.store = SharedKVPool(cfg, n_blocks, block_size,
                                      shards=shards,
                                      pool_sharding=pool_sharding)
            self.owns_store = True
        self.block_size = self.store.block_size
        self.shards = self.store.shards
        self.alloc = self.store.alloc
        self.slot_blocks: List[List[int]] = [[] for _ in range(n_slots)]
        self._tables: Optional[jax.Array] = None
        if self.bytes_per_block * self.alloc.usable_blocks <= 0:
            raise ValueError("empty paged pool")

    # ------------------------------------------------------------- #
    @property
    def pools(self):
        """Device pools live on the (possibly shared) store so a functional
        update through any attached engine is visible to all of them."""
        return self.store.pools

    @pools.setter
    def pools(self, new) -> None:
        self.store.pools = new

    # ------------------------------------------------------------- #
    @property
    def bytes_per_block(self) -> int:
        """Global pool bytes per block (``leaf.nbytes`` on a sharded array
        reports global bytes — summed over every shard's slice)."""
        n = self.alloc.n_blocks
        return sum(leaf.nbytes // n for leaf in jax.tree.leaves(self.pools))

    @property
    def bytes_per_block_per_shard(self) -> int:
        """HBM bytes one shard's device pays per block (== global for
        tp=1 and for replicated MLA pools)."""
        return self.bytes_per_block // kv_shard_divisor(self.cfg, self.shards)

    @property
    def bytes_per_token(self) -> int:
        return self.bytes_per_block // self.block_size

    def kv_bytes_in_use(self, blocks: Optional[int] = None) -> int:
        n = self.alloc.in_use if blocks is None else blocks
        return n * self.bytes_per_block

    def kv_bytes_in_use_per_shard(self, blocks: Optional[int] = None) -> int:
        n = self.alloc.in_use if blocks is None else blocks
        return n * self.bytes_per_block_per_shard

    @property
    def tables(self) -> jax.Array:
        if self._tables is None:
            rows = []
            for blocks in self.slot_blocks:
                row = blocks + [NO_BLOCK] * (self.max_blocks - len(blocks))
                rows.append(row)
            self._tables = jnp.asarray(rows, jnp.int32)
        return self._tables

    def _dirty(self) -> None:
        self._tables = None

    # ------------------------------------------------------------- #
    def blocks_for_tokens(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def slot_capacity(self, slot: int) -> int:
        """Token positions writable with the blocks currently attached."""
        return len(self.slot_blocks[slot]) * self.block_size

    def attach(self, slot: int, bid: int) -> None:
        blocks = self.slot_blocks[slot]
        if len(blocks) >= self.max_blocks:
            raise MemoryError(f"slot {slot} exceeds max_blocks {self.max_blocks}")
        blocks.append(bid)
        self._dirty()

    def grow(self, slot: int) -> bool:
        """Allocate + attach one block; False when the pool is exhausted
        (caller preempts a victim and retries)."""
        bid = self.alloc.alloc()
        if bid is None:
            return False
        self.attach(slot, bid)
        return True

    def truncate(self, slot: int, keep_blocks: int) -> int:
        """Speculative-decoding rollback: free ``slot``'s tail blocks beyond
        the first ``keep_blocks`` (blocks that only ever held rejected
        verify writes). Tail blocks are private by the scheduler's write
        discipline — grown fresh for decode, never hash-registered — so
        freeing returns them straight to the free list. Returns the number
        of blocks released."""
        blocks = self.slot_blocks[slot]
        n = 0
        while len(blocks) > keep_blocks:
            self.alloc.free(blocks.pop())
            n += 1
        if n:
            self._dirty()
        return n

    def release_slot(self, slot: int) -> None:
        for bid in self.slot_blocks[slot]:
            self.alloc.free(bid)
        self.slot_blocks[slot] = []
        self._dirty()

    def make_writable(self, slot: int, idx: int) -> None:
        """Copy-on-write the ``idx``-th block of ``slot`` if it is shared
        or published; pool contents are copied block-to-block."""
        bid = self.slot_blocks[slot][idx]
        new, copied = self.alloc.ensure_writable(bid)
        if copied:
            self.pools = jax.tree.map(
                lambda p: _copy_block_leaf(p, bid, new), self.pools)
            self.slot_blocks[slot][idx] = new
            self._dirty()

    # ------------------------------------------------------------- #
    def scatter_prefill(self, slot: int, dense_cache: Any,
                        n_tokens: int) -> List[int]:
        """Move a dense batch-1 prefill cache (leaves ``[L, 1, S_pad, ...]``)
        into freshly allocated blocks for ``slot``. The scatter always
        writes ``pow2_bucket(n_blocks_needed)`` block ids (padded with the
        reserved trash block) so only O(log max_blocks) shapes compile."""
        need = self.blocks_for_tokens(n_tokens)
        ids = []
        for _ in range(need):
            bid = self.alloc.alloc()
            if bid is None:
                for b in ids:
                    self.alloc.free(b)
                raise MemoryError("pool exhausted during prefill scatter")
            ids.append(bid)
        m = pow2_bucket(need, floor=1)
        padded = ids + [TRASH_BLOCK] * (m - need)
        idv = jnp.asarray(padded, jnp.int32)
        s_pad = jax.tree.leaves(dense_cache)[0].shape[2]
        if s_pad < m * self.block_size:
            pad_amt = m * self.block_size - s_pad
            dense_cache = jax.tree.map(
                lambda d: jnp.pad(d, [(0, 0), (0, 0), (0, pad_amt)]
                                  + [(0, 0)] * (d.ndim - 3)), dense_cache)
        self.pools = jax.tree.map(
            lambda p, d: _scatter_leaf(p, d, idv), self.pools, dense_cache)
        for bid in ids:
            self.attach(slot, bid)
        return ids

    # ------------------------------------------------------------- #
    def export_blocks(self, slot: int) -> Tuple[int, ...]:
        """Retain and return ``slot``'s blocks for handoff. Ownership of one
        reference per block moves to the caller; the slot keeps its own
        references until ``release_slot`` drops them."""
        ids = tuple(self.slot_blocks[slot])
        for bid in ids:
            self.alloc.retain(bid)
        return ids

    def import_blocks(self, slot: int, ids: Sequence[int]) -> None:
        """Attach exported blocks to an (empty) slot's table. The caller's
        references transfer to the table — no refcount change."""
        assert not self.slot_blocks[slot], f"slot {slot} not empty"
        for bid in ids:
            assert self.alloc.refcount(bid) >= 1, f"import of freed block {bid}"
            self.attach(slot, bid)

    def reset(self) -> None:
        """Engine warmup / teardown. An engine attached to a shared store
        only drops its own slots — resetting the shared allocator out from
        under peer engines would corrupt their tables (the router resets the
        store once, after quiescing every engine)."""
        if self.owns_store:
            self.alloc.reset()
        else:
            for slot in range(self.n_slots):
                self.release_slot(slot)
        self.slot_blocks = [[] for _ in range(self.n_slots)]
        self._dirty()


# ------------------------------------------------------------------ #
# Sizing helpers (fleet memory accounting)
# ------------------------------------------------------------------ #
def kv_shard_divisor(cfg: ModelConfig, shards: int = 1) -> int:
    """How many ways the cache payload actually splits under ``shards``-way
    tensor parallelism: GQA caches shard on the kv-head axis, MLA latent
    caches are head-free and replicate on every shard (divisor 1)."""
    if shards <= 1 or cfg.attention == "mla":
        return 1
    if cfg.n_kv_heads % shards:
        return 1
    return shards


def kv_bytes_per_token(cfg: ModelConfig, shards: int = 1) -> int:
    """Per-token, per-layer KV bytes for ``cfg``'s resolved precision tier —
    the single accounting rule shared by ``kv_bytes_per_block`` (admission
    budgeting, fleet ``kv_budget_bytes``) and the benchmarks'
    ``kv_hbm_bytes_per_req``.

        mla    (kv_lora_rank + qk_rope_dim) * itemsize   (no quantized tier)
        fp     2 * Hkv * hd * itemsize
        int8   2 * Hkv * (hd + 4)                 payload + per-head f32 scale
        int4   2 * Hkv * (hd/2 + 2 * n_groups)    nibbles + f16 group scales

    ``shards`` > 1 returns the *per-shard* bytes under tensor-parallel
    serving: GQA tiers carry ``Hkv / shards`` local heads (payload AND
    scale rows both ride the head axis, so every tier divides exactly);
    MLA caches replicate and keep their full size per shard.
    """
    hd = cfg.resolved_head_dim
    if cfg.attention == "mla":
        return int((cfg.kv_lora_rank + cfg.qk_rope_dim)
                   * jnp.dtype(cfg.activation_dtype).itemsize)
    hkv = cfg.n_kv_heads // kv_shard_divisor(cfg, shards)
    prec = cfg.kv_precision
    if prec == "int4":
        from repro.kernels.quantize import kv_group_size

        n_groups = hd // kv_group_size(hd)
        return int(2 * hkv * (hd // 2 + 2 * n_groups))
    if prec == "int8":
        return int(2 * hkv * (hd + 4))
    return int(2 * hkv * hd
               * jnp.dtype(cfg.activation_dtype).itemsize)


def kv_bytes_per_block(cfg: ModelConfig, block_size: int,
                       shards: int = 1) -> int:
    """Per-block HBM bytes across all layers — the unit of the fleet's
    per-device KV budget (``EnginePool.kv_budget_bytes``). With
    ``shards`` > 1: bytes each shard's device pays per pool block."""
    return int(cfg.n_layers * block_size * kv_bytes_per_token(cfg, shards))


def blocks_for_budget(cfg: ModelConfig, block_size: int,
                      budget_bytes: int, floor: int = 2,
                      shards: int = 1) -> int:
    """How many pool blocks fit a byte budget (>= ``floor`` usable).

    ``budget_bytes`` is per *device*; under tensor parallelism each device
    holds only its head shard of every block, so the same budget admits up
    to ``shards``x more blocks (MLA pools replicate — no gain)."""
    per = kv_bytes_per_block(cfg, block_size, shards)
    return max(floor + 1, budget_bytes // max(per, 1))
