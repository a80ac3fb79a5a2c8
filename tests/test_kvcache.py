"""KV-cache v2 unit tests: block allocator invariants (refcounts, LRU
eviction, copy-on-write, prefix hashes) including a property-based pass
over random op interleavings, pool scatter/gather round-trips, and sizing
helpers."""
import jax
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st
from repro import configs as C
from repro.models import init_params, prefill
from repro.serving.kvcache import (BlockAllocator, PagedKVCache,
                                   blocks_for_budget, hash_prompt_blocks,
                                   kv_bytes_per_block, kv_bytes_per_token,
                                   paged_supported, pow2_bucket)


# ------------------------------------------------------------------ #
# BlockAllocator
# ------------------------------------------------------------------ #
def test_alloc_free_roundtrip():
    a = BlockAllocator(5, 4)               # block 0 reserved -> 4 usable
    ids = [a.alloc() for _ in range(4)]
    assert sorted(ids) == [1, 2, 3, 4]
    assert a.alloc() is None               # exhausted
    assert a.in_use == 4 and a.n_free == 0
    for bid in ids:
        a.free(bid)
    assert a.n_free == 4 and a.in_use == 0
    assert a.stats.peak_in_use == 4


def test_refcount_sharing_and_release():
    a = BlockAllocator(4, 4)
    bid = a.alloc()
    a.retain(bid)
    assert a.refcount(bid) == 2
    a.free(bid)
    assert a.refcount(bid) == 1            # still held by the other owner
    assert a.n_free == 2                   # not returned yet
    a.free(bid)
    assert a.refcount(bid) == 0 and a.n_free == 3


def test_double_free_asserts():
    a = BlockAllocator(3, 4)
    bid = a.alloc()
    a.free(bid)
    with pytest.raises(AssertionError):
        a.free(bid)


def test_prefix_registry_cache_and_revive():
    a = BlockAllocator(4, 4)
    bid = a.alloc()
    a.register(bid, 1234)
    a.free(bid)                            # refcount 0 -> cached LRU
    assert a.n_cached == 1 and a.n_free == 2
    hit = a.lookup(1234)
    assert hit == bid and a.refcount(bid) == 1   # revived
    assert a.lookup(9999) is None
    # a second hit while referenced just bumps the refcount
    assert a.lookup(1234) == bid and a.refcount(bid) == 2


def test_lru_eviction_order():
    a = BlockAllocator(4, 4)               # 3 usable
    b1, b2, b3 = a.alloc(), a.alloc(), a.alloc()
    a.register(b1, 1)
    a.register(b2, 2)
    a.free(b1)
    a.free(b2)
    a.free(b3)                             # unregistered -> plain free list
    # free list is preferred; then the LRU cached block (b1) is evicted
    assert a.alloc() == b3
    got = a.alloc()
    assert got == b1 and a.stats.evictions == 1
    assert a.lookup(1) is None             # b1's hash entry dropped
    assert a.lookup(2) == b2               # b2 survived


def test_copy_on_write():
    a = BlockAllocator(6, 4)
    bid = a.alloc()
    same, copied = a.ensure_writable(bid)
    assert same == bid and not copied      # exclusive + unpublished
    a.retain(bid)                          # now shared
    new, copied = a.ensure_writable(bid)
    assert copied and new != bid
    assert a.refcount(bid) == 1 and a.refcount(new) == 1
    assert a.stats.cow_copies == 1
    # published blocks also trigger CoW even when exclusively held
    pub = a.alloc()
    a.register(pub, 7)
    new2, copied2 = a.ensure_writable(pub)
    assert copied2 and new2 != pub
    assert a.lookup(7) == pub              # the published copy still serves


def test_hash_chain_prefix_property():
    h1 = hash_prompt_blocks([1, 2, 3, 4, 5, 6, 7, 8], 4)
    h2 = hash_prompt_blocks([1, 2, 3, 4, 9, 9, 9, 9], 4)
    h3 = hash_prompt_blocks([1, 2, 3, 4, 5, 6, 7, 8, 11], 4)
    assert len(h1) == 2                    # full blocks only
    assert h1[0] == h2[0] and h1[1] != h2[1]   # shared prefix, split tail
    assert h3[:2] == h1                    # longer prompt extends the chain


def test_pow2_bucket():
    assert pow2_bucket(1) == 16            # floor
    assert pow2_bucket(16) == 16
    assert pow2_bucket(17) == 32
    assert pow2_bucket(100) == 128


# ------------------------------------------------------------------ #
# Property-based allocator hardening (hypothesis via the compat shim)
# ------------------------------------------------------------------ #
def _check_allocator_invariants(a, live):
    """The allocator's conservation laws against the reference model
    ``live`` (block id -> expected refcount):

      * every usable block is in EXACTLY one of free / cached / live;
      * free + cached + live == pool size;
      * per-block refcounts match the model (0 outside ``live``);
      * the trash block 0 is never handed out.
    """
    free = set(a._free)
    cached = set(a._cached.values())
    owned = set(live)
    assert 0 not in owned
    assert len(free) == a.n_free, "duplicate ids on the free list"
    assert len(cached) == a.n_cached
    assert free | cached | owned == set(range(1, a.n_blocks))
    assert not (free & cached) and not (free & owned) and not (cached & owned)
    assert a.n_free + a.n_cached + a.in_use == a.usable_blocks
    assert a.in_use == len(owned)
    for bid in range(1, a.n_blocks):
        assert a.refcount(bid) == live.get(bid, 0), bid
    for h, bid in a._by_hash.items():
        assert a._hash[bid] == h, "hash index out of sync with block hash"


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 40), n_blocks=st.integers(3, 24),
       n_ops=st.integers(40, 160))
def test_allocator_random_interleavings(seed, n_blocks, n_ops):
    """Random alloc/retain/free/register/lookup/peek/CoW interleavings
    must preserve refcount conservation, the free/cached/live partition,
    and no-double-hand-out — the serving stack's memory-safety core."""
    import random

    rng = random.Random(seed)
    a = BlockAllocator(n_blocks, 4)
    live = {}                               # bid -> model refcount
    issued_hashes = []
    next_hash = iter(range(10_000, 10_000 + n_ops))
    for _ in range(n_ops):
        op = rng.choice(["alloc", "alloc", "retain", "free", "free",
                         "register", "lookup", "peek", "cow"])
        if op == "alloc":
            before = a.available()
            bid = a.alloc()
            if bid is None:
                assert before == 0, "alloc failed with blocks available"
            else:
                assert bid not in live and bid != 0
                live[bid] = 1
        elif op == "retain" and live:
            bid = rng.choice(sorted(live))
            a.retain(bid)
            live[bid] += 1
        elif op == "free" and live:
            bid = rng.choice(sorted(live))
            a.free(bid)
            live[bid] -= 1
            if not live[bid]:
                del live[bid]
        elif op == "register" and live:
            bid = rng.choice(sorted(live))
            if issued_hashes and rng.random() < 0.3:
                # re-register under an existing hash: exercises both the
                # mapping-already-taken early return and old-hash retirement
                h = rng.choice(issued_hashes)
            else:
                h = next(next_hash)
                issued_hashes.append(h)
            a.register(bid, h)
        elif op == "lookup" and issued_hashes:
            h = rng.choice(issued_hashes)
            bid = a.lookup(h)
            if bid is None:
                assert h not in a._by_hash, "lookup missed a live mapping"
            else:
                live[bid] = live.get(bid, 0) + 1
        elif op == "peek" and issued_hashes:
            snap = (a.n_free, a.n_cached, a.in_use, list(a._ref))
            a.peek(rng.choice(issued_hashes))
            assert snap == (a.n_free, a.n_cached, a.in_use, list(a._ref)), \
                "peek mutated allocator state"
        elif op == "cow" and live:
            bid = rng.choice(sorted(live))
            shared = live[bid] > 1 or a._hash[bid] is not None
            try:
                new, copied = a.ensure_writable(bid)
            except MemoryError:
                assert a.available() == 0   # only legal when exhausted
                continue
            assert copied == shared
            if copied:
                live[bid] -= 1
                if not live[bid]:
                    del live[bid]
                assert new not in live
                live[new] = 1
            else:
                assert new == bid
        _check_allocator_invariants(a, live)
    # drain: releasing every reference returns the whole pool
    for bid, n in list(live.items()):
        for _ in range(n):
            a.free(bid)
    _check_allocator_invariants(a, {})


# ------------------------------------------------------------------ #
# PagedKVCache pools
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def cfg_params():
    cfg = C.smoke_config("mistral-nemo-12b").with_overrides(dtype="float32")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def test_paged_supported_guards():
    assert paged_supported(C.smoke_config("mistral-nemo-12b")) is None
    assert paged_supported(C.smoke_config("deepseek-v2-236b")) is None  # MLA
    assert paged_supported(C.smoke_config("mamba2-780m")) is not None   # ssm
    assert paged_supported(C.smoke_config("recurrentgemma-9b")) is not None
    assert paged_supported(C.smoke_config("musicgen-large")) is not None


def test_scatter_prefill_roundtrip(cfg_params):
    """Dense prefill scattered into blocks must reproduce the dense cache
    values exactly when gathered back through the block table."""
    cfg, params = cfg_params
    kv = PagedKVCache(cfg, n_slots=2, n_blocks=8, block_size=4,
                      max_blocks_per_seq=6)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 10),
                                0, cfg.vocab_size)
    _, dense = prefill(params, {"tokens": tokens}, cfg, pad_to=16)
    kv.scatter_prefill(0, dense, 10)
    assert len(kv.slot_blocks[0]) == 3     # ceil(10 / 4)
    tab = kv.tables
    assert tab.shape == (2, 6)
    assert (tab[1] == -1).all()            # slot 1 untouched
    # gather back and compare to the dense leaf, token for token
    k_pool = kv.pools["layers"][0]         # [L, N, bs, H, hd]
    k_dense = dense["layers"][0]           # [L, 1, S_pad, H, hd]
    gathered = k_pool[:, kv.slot_blocks[0]].reshape(
        k_pool.shape[0], -1, *k_pool.shape[3:])
    assert jnp.array_equal(gathered[:, :10], k_dense[:, 0, :10])


def test_release_returns_blocks(cfg_params):
    cfg, _ = cfg_params
    kv = PagedKVCache(cfg, n_slots=1, n_blocks=6, block_size=4,
                      max_blocks_per_seq=5)
    for _ in range(3):
        assert kv.grow(0)
    assert kv.alloc.in_use == 3
    kv.release_slot(0)
    assert kv.alloc.in_use == 0 and kv.slot_blocks[0] == []
    assert (kv.tables == -1).all()


def test_truncate_frees_tail_blocks_only(cfg_params):
    """Speculative rollback primitive: truncate drops tail blocks back to
    the free pool and leaves the kept prefix (and other slots) alone."""
    cfg, _ = cfg_params
    kv = PagedKVCache(cfg, n_slots=2, n_blocks=10, block_size=4,
                      max_blocks_per_seq=6)
    for _ in range(4):
        assert kv.grow(0)
    assert kv.grow(1)
    kept = list(kv.slot_blocks[0][:2])
    assert kv.truncate(0, 2) == 2
    assert kv.slot_blocks[0] == kept
    assert kv.alloc.in_use == 3            # 2 kept + slot 1's block
    assert kv.truncate(0, 2) == 0          # idempotent at the target size
    assert (kv.tables[0, 2:] == -1).all()


def test_make_writable_copies_pool_contents(cfg_params):
    cfg, params = cfg_params
    kv = PagedKVCache(cfg, n_slots=2, n_blocks=8, block_size=4,
                      max_blocks_per_seq=4)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 4),
                                0, cfg.vocab_size)
    _, dense = prefill(params, {"tokens": tokens}, cfg, pad_to=4)
    kv.scatter_prefill(0, dense, 4)
    bid = kv.slot_blocks[0][0]
    kv.alloc.retain(bid)                   # simulate sharing with slot 1
    kv.slot_blocks[1] = [bid]
    kv._dirty()
    before = kv.pools["layers"][0][:, bid]
    kv.make_writable(0, 0)
    new = kv.slot_blocks[0][0]
    assert new != bid and kv.slot_blocks[1] == [bid]
    assert jnp.array_equal(kv.pools["layers"][0][:, new], before)


def test_sizing_helpers(cfg_params):
    cfg, _ = cfg_params
    per = kv_bytes_per_block(cfg, 16)
    kv = PagedKVCache(cfg, n_slots=1, n_blocks=4, block_size=16,
                      max_blocks_per_seq=2)
    assert per == kv.bytes_per_block
    assert blocks_for_budget(cfg, 16, 10 * per) == 10
    assert blocks_for_budget(cfg, 16, 0) == 3      # floor
    # int8 blocks are ~4x smaller than fp32 (payload byte + f32 scale)
    per8 = kv_bytes_per_block(cfg.with_overrides(kv_cache_int8=True), 16)
    assert per8 < per / 2
    # int4 nibbles + f16 group scales land under 0.55x int8 (the serving
    # bench's gated kv_hbm_bytes_per_req ratio)
    per4 = kv_bytes_per_block(
        cfg.with_overrides(kv_cache_precision="int4"), 16)
    assert per4 <= 0.55 * per8


def test_kv_bytes_per_token_matches_pools():
    """The accounting helper is the single sizing rule: for every precision
    tier it must equal the actual per-token bytes of the pools the cache
    allocates (nbytes summed over leaves / blocks / block_size)."""
    base = C.smoke_config("mistral-nemo-12b").with_overrides(dtype="float32")
    for prec in ("fp", "int8", "int4"):
        cfg = base.with_overrides(kv_cache_precision=prec)
        kv = PagedKVCache(cfg, n_slots=1, n_blocks=4, block_size=16,
                          max_blocks_per_seq=2)
        leaves = jax.tree.leaves(kv.pools)
        nbytes = sum(x.nbytes for x in leaves)
        n_blocks = leaves[0].shape[1]
        per_tok = nbytes // (cfg.n_layers * n_blocks * 16)
        assert per_tok == kv_bytes_per_token(cfg), prec
