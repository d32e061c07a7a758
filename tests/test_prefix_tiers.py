"""Hierarchical prefix-cache suite: host spill tier, swap-in, replication.

What the tier machinery must preserve (ISSUE acceptance):

  (a) EXTENDED CONSERVATION — at every step of a random
      alloc/evict/spill/swap-in/replicate walk, the device blocks
      partition exactly: free + referenced == n_blocks, every block's
      refcount equals its slot-table references plus its prefix-cache
      copies, the cache's block accounting (cached + in-flight swap-ins)
      matches the tree, and `host_bytes` equals the sum of every node's
      held snapshot;
  (b) BITWISE STREAM PARITY — a request whose matched prefix was evicted
      to the host tier performs ZERO prefill forwards over that prefix
      and emits a greedy bf16 stream bitwise-equal to the cold run
      (cold == device-hot == spill-hot); quantized (kv_quant) pools spill
      and swap the packed bytes verbatim, so their spill-hot stream is
      byte-exact against device-hot too;
  (c) cross-shard replication copies hot prefixes into peer shards
      without ever evicting, and the copies adopt like home-shard ones.

Strategies come from tests/_hypothesis_compat.py when hypothesis is absent
(offline container): examples are seeded by the test's qualified name, so
failures reproduce deterministically.
"""

import random

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: seeded-random fallback shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.configs import registry
from repro.configs.base import ArchConfig
from repro.models import lm
from repro.serve.engine import EngineConfig, Request, ServeEngine, Unservable
from repro.serve.kv_pool import KVPool, OutOfBlocks, PackedKV, PackedKVPair
from repro.serve.prefix_cache import PrefixCache

PACKED = (PackedKV, PackedKVPair)   # the quantized pool's token-kind leaves

pytestmark = pytest.mark.serve

N_SLOTS, MAX_LEN, BLOCK = 4, 32, 4


def _tiny_cfg() -> ArchConfig:
    return ArchConfig(name="tier-test", family="dense", n_layers=1,
                      d_model=32, n_heads=2, n_kv_heads=2, d_ff=32, vocab=64,
                      head_dim=16)


def _pool(n_blocks=16, n_shards=2) -> KVPool:
    return KVPool(_tiny_cfg(), N_SLOTS, MAX_LEN, paged=True,
                  block_size=BLOCK, n_blocks=n_blocks, n_shards=n_shards)


def _tier_conserved(pool: KVPool, cache: PrefixCache) -> None:
    """The extended conservation invariant, checked structurally."""
    # every block's refcount == its slot-table references + cache copies
    table_refs = np.zeros(pool.n_blocks, np.int64)
    for s in range(pool.n_slots):
        for b in pool._table[s]:
            if b != pool.sentinel:
                table_refs[b] += 1
    cache_refs = np.zeros(pool.n_blocks, np.int64)
    host_bytes = 0

    def walk(n):
        nonlocal host_bytes
        for c in n.children.values():
            for b in c.blocks.values():
                cache_refs[b] += 1
            if c.host is not None:
                host_bytes += c.host_bytes
            walk(c)

    walk(cache.root)
    np.testing.assert_array_equal(np.asarray(pool._ref, np.int64),
                                  table_refs + cache_refs)
    # device partition: free + referenced == n_blocks (no block in both)
    live = int((np.asarray(pool._ref) > 0).sum())
    assert pool.free_block_count + live == pool.n_blocks
    assert all(pool.refcount(b) == 0 for b in pool._free)
    # cache-side block accounting matches the tree exactly
    assert (cache.cached_blocks() + cache.inflight_swaps
            == int(cache_refs.sum()))
    # host-tier byte accounting matches the held snapshots exactly
    assert host_bytes == cache.host_bytes


def _admit(pool, cache, slot, prompt, total):
    """Engine-shaped hot admission against a bare pool: match, pin,
    materialize on the slot's shard, adopt + COW, prefill-equivalent
    ensure. Returns the pinned adopt path (to release at retirement) or
    None when the admission failed and everything was rolled back."""
    m = cache.match(prompt)
    mtoks, adopt, tail = m.plan(len(prompt) - 1, BLOCK)
    pinned = adopt + ([tail] if tail is not None else [])
    use = mtoks > 0
    if use:
        cache.acquire(pinned)
        try:
            cache.materialize(pinned, pool.shard_of_slot(slot))
        except OutOfBlocks:
            cache.release(pinned)
            use = False
    try:
        pool.commit(slot, total)
    except OutOfBlocks:
        if use:
            cache.release(pinned)
        return None
    pins = []
    if use:
        sh = pool.shard_of_slot(slot)
        try:
            if adopt:
                pool.adopt_prefix(slot, [n.blocks[sh] for n in adopt],
                                  len(adopt) * BLOCK)
            if tail is not None:
                pool.cow_block(slot, tail.blocks[sh])
            pool.ensure(slot, mtoks)
        except OutOfBlocks:
            cache.release(pinned)
            pool.release(slot)
            return None
        if tail is not None:
            cache.release([tail])  # private COW copy made; unpin the tail
        pins = adopt
        cache.record(m)
    try:
        pool.ensure(slot, total)  # the prefill the engine would run
    except OutOfBlocks:
        pass  # partially backed is fine for the walk
    return pins


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_tier_fuzz_extended_conservation(seed):
    """Property walk over admit(hot/cold)/retire/evict-spill/swap-in/
    replicate/trim/complete sequences on a 2-shard pool: the extended
    conservation invariant holds after EVERY operation."""
    rng = random.Random(seed)
    budget = rng.choice([None, 6000, 20000])
    pool = _pool(n_blocks=16, n_shards=2)
    cache = PrefixCache(pool, spill=True, host_budget_bytes=budget,
                        replicate_hits=2)
    bound: dict[int, tuple[list[int], list]] = {}  # slot -> (prompt, pins)
    for _ in range(60):
        op = rng.choice(["admit", "retire", "evict", "replicate",
                         "complete", "admit", "retire"])
        if op == "admit":
            free = [s for s in range(N_SLOTS) if s not in bound]
            if not free:
                continue
            s = rng.choice(free)
            total = rng.randint(5, MAX_LEN)
            # tiny alphabet: prompts collide, the tree actually shares
            prompt = [rng.randrange(4) for _ in range(total)]
            pins = _admit(pool, cache, s, prompt, total)
            if pins is not None:
                bound[s] = (prompt, pins)
        elif op == "retire" and bound:
            s = rng.choice(list(bound))
            prompt, pins = bound.pop(s)
            cache.insert(prompt[:pool.length(s)], s)
            if pins:
                cache.release(pins)
            pool.release(s)
        elif op == "evict":
            cache.evict(rng.choice([None, 0, 1]), rng.randint(1, 4))
        elif op == "replicate":
            cache.replicate_hot(budget=rng.randint(1, 3))
        elif op == "complete":
            cache.complete_swaps()
        _tier_conserved(pool, cache)
        if budget is not None:
            assert cache.host_bytes <= budget
    # teardown: release slots, drop the whole cache — everything comes back
    for s, (prompt, pins) in bound.items():
        if pins:
            cache.release(pins)
        pool.release(s)
    cache.complete_swaps()
    cache.evict(None, pool.n_blocks)
    _tier_conserved(pool, cache)
    assert pool.free_block_count == pool.n_blocks


def test_spill_on_evict_keeps_node_matchable():
    """Eviction under spill snapshots bytes host-side and keeps the path
    matchable; without spill the path vanishes. Swap-in restores device
    copies and the refcount partition."""
    pool = _pool()
    cache = PrefixCache(pool, spill=True)
    prompt = [1] * 12
    pool.commit(0, 12)
    pool.ensure(0, 12)
    cache.insert(prompt, 0)
    pool.release(0)
    assert cache.cached_blocks() == 3
    freed = cache.evict(None, 99)
    assert freed == 3
    assert cache.cached_blocks() == 0
    assert cache.host_nodes() == 3
    assert cache.host_bytes > 0
    assert cache.stats["spilled_blocks"] == 3
    m = cache.match(prompt)
    assert m.tokens == 12                    # still matchable, host-only
    cache.acquire(m.nodes)
    assert cache.materialize(m.nodes, 1) == 3   # swap in on the OTHER shard
    assert cache.inflight_swaps == 3
    assert cache.cached_blocks() == 0        # in-flight until tick boundary
    cache.complete_swaps()
    assert cache.cached_blocks() == 3
    assert all(1 in n.blocks for n in m.nodes)
    cache.release(m.nodes)
    _tier_conserved(pool, cache)


def test_spill_hint_weights_host_only_tokens_half():
    """Scheduler admission hint: resident matched tokens count in full,
    host-only (spilled) ones half — a swap-in is cheaper than prefill but
    not free (serve/scheduler.py cache-aware ordering)."""
    pool = _pool()
    cache = PrefixCache(pool, spill=True)
    prompt = [2] * 8
    pool.commit(0, 8)
    pool.ensure(0, 8)
    cache.insert(prompt, 0)
    pool.release(0)
    assert cache.hint_tokens(cache.match(prompt)) == 8
    cache.evict(None, 99)                     # both blocks to the host tier
    assert cache.hint_tokens(cache.match(prompt)) == 4
    drop = PrefixCache(_pool(), spill=False)
    assert drop.hint_tokens(drop.match(prompt)) == 0  # dropped: no match


def test_replicate_hot_copies_into_peer_shard():
    """Nodes matched past `replicate_hits` get device copies on peer
    shards through the host tier — free blocks only, never evicting —
    and the replicas adopt exactly like home-shard blocks."""
    pool = _pool(n_blocks=16, n_shards=2)
    cache = PrefixCache(pool, spill=True, replicate_hits=2)
    prompt = [3] * 8
    pool.commit(0, 8)                          # slot 0 homes on shard 0
    pool.ensure(0, 8)
    cache.insert(prompt, 0)
    pool.release(0)
    assert cache.replicate_hot(budget=8) == 0  # not hot yet
    for _ in range(2):
        cache.record(cache.match(prompt))
    done = cache.replicate_hot(budget=8)
    assert done == 2
    assert cache.stats["replicated_blocks"] == 2
    cache.complete_swaps()
    m = cache.match(prompt)
    assert all(set(n.blocks) == {0, 1} for n in m.nodes)
    _tier_conserved(pool, cache)
    # the shard-1 replicas adopt for a shard-1 slot with zero swap-ins
    cache.acquire(m.nodes)
    assert cache.materialize(m.nodes, 1) == 0
    pool.commit(2, 10)                         # slot 2 homes on shard 1
    pool.adopt_prefix(2, [n.blocks[1] for n in m.nodes], 8)
    _tier_conserved(pool, cache)
    pool.release(2)
    cache.release(m.nodes)
    _tier_conserved(pool, cache)


def test_replication_never_evicts():
    """replicate_hot on a shard with an empty free list is a no-op — it
    must not trigger the evict hook to make room."""
    pool = _pool(n_blocks=8, n_shards=2)       # 4 blocks per shard
    cache = PrefixCache(pool, spill=True, replicate_hits=1)
    prompt = [1] * 8
    pool.commit(0, 8)
    pool.ensure(0, 8)
    cache.insert(prompt, 0)
    pool.release(0)
    cache.record(cache.match(prompt))
    pool.commit(2, 16)                         # slot 2 drains shard 1 fully
    pool.ensure(2, 16)
    assert pool.free_blocks_in_shard(1) == 0
    evicted0 = cache.stats["evicted_blocks"]
    assert cache.replicate_hot(budget=8) == 0
    assert cache.stats["evicted_blocks"] == evicted0
    pool.release(2)
    _tier_conserved(pool, cache)


def test_host_budget_trims_lru_snapshots():
    """`host_budget_bytes` bounds the tier: LRU snapshots are dropped
    (nodes with device copies keep matchability; a host-only childless
    node leaves the tree, bumping the epoch)."""
    pool = _pool()
    cache = PrefixCache(pool, spill=True)
    one_block = None
    for s, base in ((0, 4), (1, 5)):
        pool.commit(s, 16)
        pool.ensure(s, 16)
        cache.insert([base] * 16, s)
        pool.release(s)
    cache.evict(None, 99)                      # 8 snapshots on the host
    assert cache.host_nodes() == 8
    one_block = cache.host_bytes // 8
    cache.host_budget_bytes = 3 * one_block
    epoch0 = cache.epoch
    cache._trim_host()
    assert cache.host_bytes <= 3 * one_block
    assert cache.epoch > epoch0                # host-only nodes left the tree
    _tier_conserved(pool, cache)


def test_spill_requires_prefix_cache():
    cfg = registry.get("yi_9b").reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="prefix_spill"):
        ServeEngine(cfg, params, EngineConfig(
            n_slots=2, max_len=64, scheme="bf16", prequant=False,
            prefix_spill=True))


def test_decode_role_rejects_prompt_submissions():
    """A decode-role engine takes Handoffs, not prompts; a both-role
    engine takes prompts, not Handoffs."""
    cfg = registry.get("yi_9b").reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    de = ServeEngine(cfg, params, EngineConfig(
        n_slots=2, max_len=64, scheme="bf16", prequant=False,
        role="decode"))
    with pytest.raises(Unservable, match="decode-role"):
        de.submit(Request(prompt=[1, 2, 3], max_new=2))
    assert de.stats["rejected"] == 1
    both = ServeEngine(cfg, params, EngineConfig(
        n_slots=2, max_len=64, scheme="bf16", prequant=False))
    with pytest.raises(ValueError, match="non-decode"):
        both.submit_handoff(None)


# --------------------------------------------------------------------------
# bitwise stream parity across tiers (acceptance criterion)
# --------------------------------------------------------------------------

def _serve_cfg():
    return registry.get("yi_9b").reduced()


def _tier_prompts(cfg):
    rng = np.random.RandomState(1)
    shared = list(map(int, rng.randint(0, cfg.vocab, 16)))
    probe = shared + list(map(int, rng.randint(0, cfg.vocab, 7)))
    return shared + list(map(int, rng.randint(0, cfg.vocab, 5))), probe


def _one_stream(eng, prompt, max_new=4):
    eng.submit(Request(prompt=prompt, max_new=max_new))
    return [r.tokens for r in eng.run()][0]


@pytest.mark.parametrize("kv_quant", [False, True],
                         ids=["bf16", "kv_quant"])
def test_spill_hot_stream_parity_and_zero_prefix_prefill(kv_quant):
    """cold == device-hot == spill-hot, token for token (bf16 exact; the
    kv_quant pool spills/swaps its packed bytes verbatim, so its spill-hot
    stream is byte-exact against device-hot AND cold — deterministic RTN
    writes the same packed block either way). The spill-hot request runs
    ZERO prefill forwards over the matched prefix: its prefill step/token
    counts equal the device-hot run's."""
    cfg = _serve_cfg()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    warm, probe = _tier_prompts(cfg)

    def engine(**kw):
        return ServeEngine(cfg, params, EngineConfig(
            n_slots=2, max_len=64, prefill_chunk=8, block_size=8,
            scheme="bf16", prequant=False, kv_quant=kv_quant, **kw))

    cold = _one_stream(engine(), probe)

    def hot_run(spill):
        eng = engine(prefix_cache=True, prefix_spill=spill)
        _one_stream(eng, warm)                 # prime the cache
        if spill:
            freed = eng.cache.evict(None, 999)
            assert freed >= 3                  # the whole warm stream left
            assert eng.cache.cached_blocks() == 0
            assert eng.cache.host_nodes() >= 3
        steps0 = eng.stats["prefill_steps"]
        toks0 = eng.stats["prefill_tokens"]
        out = _one_stream(eng, probe)
        return (out, eng.stats["prefill_steps"] - steps0,
                eng.stats["prefill_tokens"] - toks0, eng)

    hot, hot_steps, hot_toks, _ = hot_run(False)
    spill_hot, spill_steps, spill_toks, eng = hot_run(True)
    assert hot == cold
    assert spill_hot == cold
    # zero prefill forwards over the prefix: identical step/token counts
    # to the device-hot run, 16 of the 23 prompt tokens never forwarded
    assert (spill_steps, spill_toks) == (hot_steps, hot_toks)
    assert spill_toks == len(probe) - 16
    assert eng.cache.stats["swapped_in_blocks"] >= 2
    assert eng.stats["prefill_skipped_tokens"] >= 16
    # pool conserved with the swapped-in blocks folded back in
    live = int((np.asarray(eng.pool._ref) > 0).sum())
    assert eng.pool.free_block_count + live == eng.pool.n_blocks


def test_kv_quant_spill_payload_stays_packed():
    """The host tier stores kv_quant blocks as packed uint8 bytes
    (`PackedKVPair` for attention layers, `PackedKV` for latent ones) —
    never dequantized — so spill-hot is byte-exact by construction and the
    snapshot costs the packed footprint, not the bf16 one."""
    cfg = _serve_cfg()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    warm, _ = _tier_prompts(cfg)
    eng = ServeEngine(cfg, params, EngineConfig(
        n_slots=2, max_len=64, prefill_chunk=8, block_size=8,
        scheme="bf16", prequant=False, kv_quant=True,
        prefix_cache=True, prefix_spill=True))
    _one_stream(eng, warm)
    eng.cache.evict(None, 999)
    packed = []

    def walk(n):
        for c in n.children.values():
            if c.host is not None:
                packed.extend(jax.tree_util.tree_leaves(
                    c.host, is_leaf=lambda x: isinstance(x, PACKED)))
            walk(c)

    walk(eng.cache.root)
    assert packed
    assert all(isinstance(p, PACKED) for p in packed)
    leaves = [a for p in packed for a in jax.tree_util.tree_leaves(p)]
    assert all(a.dtype.itemsize == 1 for a in leaves)  # packed bytes only


def test_disagg_pair_streams_match_monolithic():
    """Role-split prefill/decode pair: bitwise-equal streams to the
    monolithic engine, zero decode steps on the prefill worker, zero
    prefill forwards on the decode worker, both pools fully reclaimed."""
    from repro.serve.frontend import make_disagg_pair
    cfg = _serve_cfg()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    prompts = [list(map(int, rng.randint(0, cfg.vocab, n)))
               for n in (9, 13)]
    econf = EngineConfig(n_slots=2, max_len=64, prefill_chunk=8,
                         scheme="bf16", prequant=False)
    mono = ServeEngine(cfg, params, econf)
    ids = [mono.submit(Request(prompt=p, max_new=5)) for p in prompts]
    res = {r.req_id: r.tokens for r in mono.run()}
    want = [res[i] for i in ids]

    pair = make_disagg_pair(cfg, params, econf)
    streamed: dict[int, list[int]] = {}
    pair.token_hook = lambda req, new, result: \
        streamed.setdefault(req.req_id, []).extend(new)
    ids2 = [pair.submit(Request(prompt=p, max_new=5)) for p in prompts]
    res2 = {r.req_id: r.tokens for r in pair.run()}
    assert [res2[i] for i in ids2] == want
    # the token hook saw one continuous per-request stream across the
    # handoff (first token from the prefill worker, rest from decode)
    assert [streamed[i] for i in ids2] == want
    assert pair.prefill.stats["decode_steps"] == 0
    assert pair.decode.stats["prefill_steps"] == 0
    assert pair.stats["finished"] == 2         # merged stats view
    for eng in (pair.prefill, pair.decode):
        assert eng.free_slots == 2
        held = eng.cache.cached_blocks() if eng.cache is not None else 0
        assert eng.pool.free_block_count + held == eng.pool.n_blocks
