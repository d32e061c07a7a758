"""Radix-tree prefix cache over the paged KV pool, with a host spill tier.

Real serving fleets see enormous shared-prompt overlap (system prompts,
few-shot preambles, multi-turn histories). The block tables of
serve/kv_pool.py already decouple logical from physical blocks, so sharing
is purely an allocator problem: this module keeps a token-level radix tree
whose nodes OWN refcounted physical blocks, and on admission the engine

  1. matches the longest cached prefix of the new prompt,
  2. aliases the fully-matched blocks READ-ONLY into the new slot's table
     (`KVPool.adopt_prefix` — their prefill is skipped entirely),
  3. copy-on-writes the block holding the first divergent token or the
     partial tail (`KVPool.cow_block` — the matched part of that block is
     reused bit-for-bit too, so the WHOLE matched prefix costs zero
     prefill forward passes).

On retirement the completed stream's full blocks are inserted; when the
pool runs out of blocks, unpinned nodes are evicted leaf-first in LRU
order (a node whose block any live slot still aliases is pinned by its
`refs` count, and a node with referenced descendants is transitively
pinned because adoption refs the whole path).

HIERARCHICAL MODE (`spill=True`): eviction under pool pressure becomes a
device->host copy instead of a drop. The evicted node keeps an IMMUTABLE
host snapshot of its block bytes (`KVPool.read_block_host` — raw PackedKV
packed bytes for quantized pools, bf16 otherwise), and a later match on
the spilled path swaps the blocks back in (`materialize`) by allocating a
fresh block and DISPATCHING the host->device write without blocking — the
copy overlaps subsequent decode ticks, and any step that reads the pool
is ordered after it by the cache pytree data dependence. A spill-hot
request therefore still skips every prefill forward over the matched
prefix, and under bf16 its stream is bitwise-equal to cold
(host->device->host is the identity). Nodes also become MULTI-SHARD: a
node may hold one device copy per shard (`blocks` maps shard -> block),
so hot prefixes past a hit-count threshold are proactively replicated
into peer shards' pools through the host tier (`replicate_hot`), and a
cross-shard match admits hot instead of cold. Host copies are immutable
snapshots and only the engine thread initiates swap-in
(docs/CONVENTIONS.md §9).

Tree shape: children are keyed by the `block_size`-token tuple a child's
block covers, so every node owns exactly ONE full physical block per
resident shard and the tree needs no edge splitting. Matching is still
TOKEN-level: a prompt that diverges inside a block gets the in-block
common prefix via COW. Exactness (docs/CONVENTIONS.md §3-5): the decode
forward is row-local and deterministic, so under `bf16` a cached block's
K/V equals what the new request's own prefill would have written, bit for
bit; quantizing schemes share an activation absmax across the batch, so
quartet2 hot runs are deterministic but not bit-comparable to cold runs
(the same caveat as spec-decode chunks and the sharded engine).

Exclusions (`supported`): dense pools have no block tables; sliding-window
pools (`reclaim_window`) free out-of-window blocks mid-sequence, so a
cached prefix is not fully resident past the window and must not be
shared; recurrent-state archs (wkv / lru) integrate the whole prefix into
O(1) slot state that blocks cannot reconstruct. With the slot-affine
sharded pool (PR 4) and `spill=False`, a prefix is only reusable by slots
homed on its shard: every node records the shard its block lives on, and
insertion never extends a path across shards (spill mode lifts both
limits via the host tier).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from repro.serve.kv_pool import KVPool, OutOfBlocks


class _Node:
    """One cached full block: `tokens` (block_size ids) -> physical copies.

    `blocks` maps shard -> device block id (one refcounted copy per shard;
    single-copy in non-spill mode). `host` holds the immutable host-tier
    snapshot (None while never spilled/replicated); `hits` counts admission
    matches through this node (replication trigger)."""

    __slots__ = ("parent", "children", "tokens", "blocks", "host",
                 "host_bytes", "hits", "refs", "last_used")

    def __init__(self, parent, tokens: tuple[int, ...], block: int,
                 shard: int, clock: int):
        self.parent = parent
        self.children: dict[tuple[int, ...], _Node] = {}
        self.tokens = tokens
        self.blocks: dict[int, int] = {} if block < 0 else {shard: block}
        self.host = None       # immutable host payload (spill tier)
        self.host_bytes = 0
        self.hits = 0
        self.refs = 0          # live slots currently aliasing this path
        self.last_used = clock

    @property
    def shard(self) -> int:
        """Home shard of the (single) device copy — non-spill introspection."""
        return next(iter(self.blocks), -1)

    @property
    def block(self) -> int:
        return next(iter(self.blocks.values()), -1)

    def available(self) -> bool:
        """Matchable: at least one device copy or a host snapshot."""
        return bool(self.blocks) or self.host is not None


@dataclass
class Match:
    """Longest cached prefix of a prompt.

    `nodes` — path of fully-matched nodes (len(nodes) * block_size tokens);
    `partial_node` / `partial` — a child whose block matches `partial` more
    tokens (0 < partial < block_size) before diverging; `tokens` — total
    matched token count. The engine caps `tokens` at len(prompt) - 1 (the
    last prompt token must be computed to produce first-token logits) and
    re-derives the alias/COW split from the capped value via `plan`.
    """
    nodes: list[_Node] = field(default_factory=list)
    partial_node: _Node | None = None
    partial: int = 0

    @property
    def tokens(self) -> int:
        return sum(len(n.tokens) for n in self.nodes) + self.partial

    @property
    def shard(self) -> int | None:
        if self.nodes:
            return self.nodes[0].shard
        if self.partial_node is not None:
            return self.partial_node.shard
        return None

    def plan(self, cap: int, block_size: int):
        """(m, adopt_nodes, tail_node) for a match capped at `cap` tokens:
        adopt_nodes' blocks alias read-only (full blocks below m), and
        tail_node (if any) supplies the COW source for m's partial block."""
        m = min(self.tokens, cap)
        full = m // block_size
        adopt = self.nodes[:full]
        tail = None
        if m % block_size:
            tail = (self.nodes[full] if full < len(self.nodes)
                    else self.partial_node)
        return m, adopt, tail


class PrefixCache:
    """Host-side radix cache bound to one KVPool (the engine's main pool).

    Pool-level laws it maintains (tests/test_kv_pool.py,
    tests/test_prefix_tiers.py):
      - a cached node holds exactly ONE pool reference per device copy
        (taken at insertion / swap-in / replication, dropped at eviction);
      - a node is evictable iff no slot aliases its path (`refs == 0`) —
        pinned nodes (and, transitively, their ancestors) never free
        blocks a live slot still reads;
      - eviction is leaf-first LRU and feeds the pool's free list through
        `KVPool._decref`, so conservation (free + referenced == n_blocks)
        holds at every step; with `spill=True` the bytes move to the host
        tier first and `host_bytes` equals the sum of every node's held
        snapshot (the extended conservation invariant).
    """

    def __init__(self, pool: KVPool, *, spill: bool = False,
                 host_budget_bytes: int | None = None,
                 replicate_hits: int | None = None, clock=None):
        if not self.supported(pool):
            raise ValueError(
                "PrefixCache requires a paged pool without a sliding-window "
                "reclaim horizon and without recurrent state kinds "
                "(dense layouts have no block table; windowed prefixes are "
                "not fully resident; wkv/lru state is not block-addressed)")
        self.pool = pool
        self.block_size = pool.block_size
        self.spill = spill
        self.host_budget_bytes = host_budget_bytes
        self.replicate_hits = replicate_hits
        self.wall = clock if clock is not None else time.perf_counter
        self.host_bytes = 0
        # swap-in writes dispatched this tick, not yet at a tick boundary:
        # their blocks are cache-held but counted separately by the
        # extended conservation invariant (engine clears via complete_swaps)
        self._inflight: list[int] = []
        self.root = _Node(None, (), -1, -1, 0)
        self._clock = 0
        # bumped whenever MATCHABILITY changes (node added/removed, host
        # snapshot dropped) — matching is topology-only, so callers may
        # reuse a Match until the epoch moves (the engine memoizes per
        # queued request instead of re-walking the radix tree every
        # scheduler tick). A spill that keeps the node available does NOT
        # bump: the memoized plan stays valid and materializes on use.
        self.epoch = 0
        self.stats = {"lookups": 0, "hits": 0, "hit_tokens": 0,
                      "inserted_blocks": 0, "evicted_blocks": 0,
                      "spilled_blocks": 0, "swapped_in_blocks": 0,
                      "replicated_blocks": 0, "swapin_s": 0.0}
        # observability hook (set by the engine with EngineConfig(obs=...));
        # mirrors the stats events into registry counters
        self.obs = None
        pool.evict_hook = self.evict

    @staticmethod
    def supported(pool: KVPool) -> bool:
        # Quantized (PackedKV) pools are supported with no special casing:
        # sharing is by PHYSICAL BLOCK, and a shared quantized block is
        # shared packed bytes — immutable once written (per-token
        # deterministic RTN), so aliasing/COW semantics are unchanged and
        # hot-vs-cold streams stay identical per storage mode
        # (docs/CONVENTIONS.md §7). The host tier spills those same packed
        # bytes verbatim, so spill-hot == device-hot byte-for-byte too.
        return pool.paged and pool.window is None and not pool.has_state_kinds

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    # ---- lookup ----------------------------------------------------------

    def record(self, match: Match | None) -> None:
        """Book one lookup (and its hit) in the stats. Called by the engine
        ONCE per successful admission — not from `match`, which may run
        several times for the same queued request (placement retries each
        tick, scheduler hint scans) and would inflate the hit rate. Pass
        None for an admission that did not USE its match (e.g. the cached
        prefix homed on a shard with no usable slot): books a miss. Hits
        bump the path's `hits` counters — the replication trigger."""
        self.stats["lookups"] += 1
        hit = match is not None and match.tokens
        if hit:
            self.stats["hits"] += 1
            self.stats["hit_tokens"] += match.tokens
            for n in match.nodes:
                n.hits += 1
            if match.partial_node is not None:
                match.partial_node.hits += 1
        if self.obs is not None:
            self.obs.on_cache_record(bool(hit), match.tokens if hit else 0)

    def hint_tokens(self, match: Match) -> int:
        """Scheduler admission hint for a match (Request.cached_hint,
        serve/scheduler.py cache-aware ordering): device-resident matched
        tokens count in full, host-only (spilled) tokens half — a swap-in
        is far cheaper than prefill but still costs an allocation and a
        host->device copy, so among equals the fully resident prefix should
        admit first. Non-spill caches hold only resident nodes: the hint is
        exactly `match.tokens`, the original behavior."""
        if not self.spill:
            return match.tokens
        t = 0
        for n in match.nodes:
            t += len(n.tokens) if n.blocks else len(n.tokens) // 2
        if match.partial_node is not None:
            t += (match.partial if match.partial_node.blocks
                  else match.partial // 2)
        return t

    def match(self, prompt: list[int]) -> Match:
        """Longest cached prefix of `prompt` (token-level; may end inside a
        block). Does NOT pin anything (call `acquire` on the planned nodes
        before allocating against the pool) and does NOT book stats (the
        engine calls `record` once per admission). Spilled (host-only)
        nodes match like resident ones — adoption materializes them."""
        bs = self.block_size
        node, nodes = self.root, []
        d = 0
        while (d + 1) * bs <= len(prompt):
            child = node.children.get(tuple(prompt[d * bs:(d + 1) * bs]))
            if child is None or not child.available():
                break
            nodes.append(child)
            node = child
            d += 1
        # partial tail: the child sharing the longest in-block prefix with
        # the remaining tokens (children are few; a linear scan is fine)
        rest = prompt[d * bs:]
        best, best_len = None, 0
        for child in node.children.values():
            if not child.available():
                continue
            n = 0
            for a, b in zip(rest, child.tokens):
                if a != b:
                    break
                n += 1
            if n > best_len:
                best, best_len = child, n
        return Match(nodes=nodes, partial_node=best, partial=best_len)

    # ---- pinning ---------------------------------------------------------

    def acquire(self, nodes: list[_Node]) -> None:
        """Pin `nodes` (a slot now aliases / is copying their blocks)."""
        clock = self._tick()
        for n in nodes:
            n.refs += 1
            n.last_used = clock

    def release(self, nodes: list[_Node]) -> None:
        clock = self._tick()
        for n in nodes:
            assert n.refs > 0, "prefix-cache release without acquire"
            n.refs -= 1
            n.last_used = clock

    # ---- host tier -------------------------------------------------------

    def _snapshot(self, node: _Node):
        """Node's immutable host payload, reading a resident device copy on
        first use. Idempotent: bytes never change once a block's positions
        are written (docs/CONVENTIONS.md §7/§9), so one snapshot serves
        every later swap-in and replication of the node."""
        if node.host is None:
            src = next(iter(node.blocks.values()))
            node.host, node.host_bytes = self.pool.read_block_host(src)
            self.host_bytes += node.host_bytes
        return node.host

    def materialize(self, nodes: list[_Node], shard: int) -> int:
        """Ensure every node has a device copy on `shard`, swapping spilled
        blocks back in from the host tier (or sideloading from a peer
        shard's copy via a fresh snapshot — the on-demand half of
        cross-shard replication). Writes are DISPATCHED, not awaited: the
        host->device copies overlap decode ticks, and the next step's pool
        reads are ordered after them by the cache data dependence. Pin the
        nodes (`acquire`) BEFORE calling — the allocations may evict, and
        unpinned path nodes could be reclaimed from under the swap-in.
        Engine-thread-only (docs/CONVENTIONS.md §9). Returns blocks
        swapped in; raises OutOfBlocks when the shard cannot hold the path.
        """
        missing = [n for n in nodes if shard not in n.blocks]
        if not missing:
            return 0
        t0 = self.wall()
        pool = self.pool
        try:
            for n in missing:
                payload = self._snapshot(n)
                blk = pool.alloc_cache_block(shard)
                pool.write_block_host(blk, payload)
                n.blocks[shard] = blk
                self._inflight.append(blk)
        except OutOfBlocks:
            # the snapshots taken so far stay; keep the tier in budget
            self._trim_host()
            raise
        dt = self.wall() - t0
        self.stats["swapped_in_blocks"] += len(missing)
        self.stats["swapin_s"] += dt
        if self.obs is not None:
            self.obs.on_cache_swap_in(len(missing), dt)
        self._trim_host()
        return len(missing)

    def complete_swaps(self) -> None:
        """Tick-boundary accounting: in-flight swap-ins become plain cached
        blocks (the device write is ordered before any dependent step read,
        so no host sync happens here). Called by the engine at the end of
        each step."""
        self._inflight.clear()

    def replicate_hot(self, budget: int = 1) -> int:
        """Proactively copy up to `budget` blocks of HOT nodes (hits past
        `replicate_hits`) into shards missing them, through the host tier.
        Opportunistic: only genuinely free blocks are used (replication
        never evicts), so a loaded shard is left alone. Bounded per tick by
        `budget` — the engine amortizes replication across ticks."""
        if (not self.spill or self.replicate_hits is None
                or self.pool.n_shards == 1 or budget <= 0):
            return 0
        pool, done = self.pool, 0
        targets = [s for s in range(pool.n_shards) if pool._frees[s]]
        if not targets:
            return 0

        def walk(node):
            nonlocal done
            for c in node.children.values():
                if done >= budget:
                    return
                if c.hits >= self.replicate_hits and c.available():
                    for s in targets:
                        if done >= budget:
                            break
                        if s in c.blocks or not pool._frees[s]:
                            continue
                        payload = self._snapshot(c)
                        blk = pool.alloc_cache_block(s)
                        pool.write_block_host(blk, payload)
                        c.blocks[s] = blk
                        self._inflight.append(blk)
                        done += 1
                walk(c)

        walk(self.root)
        if done:
            self.stats["replicated_blocks"] += done
            if self.obs is not None:
                self.obs.on_cache_replicate(done)
            self._trim_host()
        return done

    def _trim_host(self) -> None:
        """Best-effort host-tier budget: drop LRU snapshots, preferring
        nodes that keep a device copy (the snapshot is re-readable); a
        host-ONLY childless node is removed outright. Host-only INNER nodes
        keep their snapshot — dropping it would orphan a cached subtree."""
        if self.host_budget_bytes is None:
            return
        while self.host_bytes > self.host_budget_bytes:
            resident, sole = [], []

            def walk(node):
                for c in node.children.values():
                    if c.host is not None and c.refs == 0:
                        if c.blocks:
                            resident.append(c)
                        elif not c.children:
                            sole.append(c)
                    walk(c)

            walk(self.root)
            pick = min(resident, key=lambda n: n.last_used) if resident \
                else min(sole, key=lambda n: n.last_used) if sole else None
            if pick is None:
                return
            self.host_bytes -= pick.host_bytes
            pick.host, pick.host_bytes = None, 0
            if not pick.blocks and not pick.children:
                del pick.parent.children[pick.tokens]
                self.epoch += 1

    # ---- insertion (request retirement) ----------------------------------

    def insert(self, tokens: list[int], slot: int) -> int:
        """Cache the FULL blocks of a retiring slot's token stream.

        Walks/extends the tree block by block: an existing node with a copy
        on the slot's shard dedups (the slot's physical block — aliased or
        independently prefilled — is simply dropped by the slot's
        subsequent `release`); a missing node adopts the slot's block with
        one cache reference, which survives the release. In spill mode an
        existing node MISSING this shard's copy adopts the slot's block as
        an additional per-shard replica (the retiring slot just proved the
        bytes exist on this shard); without spill, paths never mix shards —
        extension stops at the first shard mismatch. Returns the number of
        newly cached blocks. Call BEFORE `pool.release(slot)`."""
        pool = self.pool
        shard = pool.shard_of_slot(slot)
        clock = self._tick()
        node, added = self.root, 0
        bs = self.block_size
        for d in range(len(tokens) // bs):
            key = tuple(tokens[d * bs:(d + 1) * bs])
            child = node.children.get(key)
            if child is not None and child.available():
                child.last_used = clock
                if shard in child.blocks:
                    node = child
                    continue
                if not self.spill:
                    break
                blk = int(pool._table[slot, d])
                if blk == pool.sentinel:
                    break
                pool.incref(blk)
                child.blocks[shard] = blk
                node = child
                added += 1
                continue
            blk = int(pool._table[slot, d])
            if blk == pool.sentinel:
                break
            pool.incref(blk)
            if child is not None:  # dead husk (trimmed): revive in place
                child.blocks = {shard: blk}
                child.last_used = clock
            else:
                child = _Node(node, key, blk, shard, clock)
                node.children[key] = child
            node = child
            added += 1
        self.stats["inserted_blocks"] += added
        if self.obs is not None:
            self.obs.on_cache_insert(added)
        if added:
            self.epoch += 1
        return added

    # ---- eviction --------------------------------------------------------

    def _evictable_leaves(self, shard: int | None):
        """Nodes whose shard-`shard` copy may be dropped: unpinned, and no
        child holds a copy on that shard (leaf-first per shard — a parent
        copy outlives its resident descendants, so an adoptable path is
        always contiguous). `shard=None` considers every resident copy."""
        out = []

        def blocked(c, sh):
            # a HOST-ONLY descendant does not pin its ancestors: in spill
            # mode an evicted child stays in the tree (matchable via its
            # snapshot), and treating it as blocking would freeze eviction
            # at the leaf fringe forever
            return any((sh in g.blocks if sh is not None else bool(g.blocks))
                       or blocked(g, sh) for g in c.children.values())

        def walk(n):
            for c in n.children.values():
                if (c.refs == 0 and not blocked(c, shard)
                        and (shard is None or shard in c.blocks)):
                    if c.blocks:
                        out.append(c)
                else:
                    walk(c)

        walk(self.root)
        return out

    def evict(self, shard: int | None, need: int) -> int:
        """Free >= `need` blocks homed on `shard` by LRU leaf eviction
        (best effort — returns the number actually freed). Also the pool's
        `evict_hook`, so an `ensure`/COW that finds the free list empty
        reclaims cache-held blocks transparently. In spill mode the bytes
        are snapshotted to the host tier FIRST (device->host copy; packed
        bytes for quantized pools) and the node stays matchable — a later
        hit swaps back in instead of re-prefilling."""
        freed = 0
        while freed < need:
            leaves = self._evictable_leaves(shard)
            if not leaves:
                break
            leaves.sort(key=lambda n: n.last_used)
            for n in leaves:
                drop = ([shard] if shard is not None
                        else sorted(n.blocks))
                for sh in drop:
                    if self.spill:
                        self._snapshot(n)
                        self.stats["spilled_blocks"] += 1
                        if self.obs is not None:
                            self.obs.on_cache_spill(1, n.host_bytes)
                    blk = n.blocks.pop(sh)
                    self.pool._decref(blk)
                    freed += 1
                    if freed >= need:
                        break
                if not n.available():
                    del n.parent.children[n.tokens]
                    self.epoch += 1
                if freed >= need:
                    break
        self.stats["evicted_blocks"] += freed
        if self.obs is not None and freed:
            self.obs.on_cache_evict(freed)
        if self.spill and freed:
            # spilling grew the host tier: enforce the budget here too, so
            # `host_bytes <= host_budget_bytes` holds after EVERY operation
            # (pinned paths are exempt from trimming, so a mid-materialize
            # eviction cannot drop the snapshot being swapped in)
            self._trim_host()
        return freed

    # ---- introspection ---------------------------------------------------

    def cached_blocks(self) -> int:
        """Device blocks the cache holds, EXCLUDING in-flight swap-ins
        (their dispatched writes complete at the next tick boundary — the
        extended conservation invariant counts them separately)."""
        n = 0

        def walk(node):
            nonlocal n
            for c in node.children.values():
                n += len(c.blocks)
                walk(c)

        walk(self.root)
        return n - len(self._inflight)

    @property
    def inflight_swaps(self) -> int:
        return len(self._inflight)

    def host_nodes(self) -> int:
        """Nodes currently holding a host-tier snapshot."""
        n = 0

        def walk(node):
            nonlocal n
            for c in node.children.values():
                n += c.host is not None
                walk(c)

        walk(self.root)
        return n
