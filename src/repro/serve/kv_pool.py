"""Block-based paged KV pool with per-sequence block tables.

Serving memory is dominated by decode caches. The pool carves each cache kind
into fixed-size blocks of `block_size` token positions and hands blocks to
sequences on demand (vLLM-style PagedAttention layout, adapted to the stacked
stage pytrees of models/lm.py):

  token kinds  ("kv", "mla"):      pool leaves (layers, n_blocks, block, ...)
  state kinds  ("wkv", "tm_prev",
                "cm_prev", "lru"): slot leaves (layers, n_slots, ...)
                                   (recurrent state is O(1) per sequence —
                                   one implicit "block" per slot)

A per-slot block table (n_slots, max_blocks) maps logical block index ->
physical pool block; unallocated entries hold the OOB sentinel `n_blocks`,
so device-side writes through them are DROPPED by the scatter and gathers
read zeros (`mode="fill"`). That single convention gives free write-masking
for inactive slots and positions beyond a sequence's allocation. (OOB-HIGH,
never -1: negative scatter indices WRAP numpy-style — docs/CONVENTIONS.md.)

With `n_shards > 1` the allocator is SLOT-AFFINE over a mesh "data" axis
(serve/engine.py multi-host mode): slots and physical blocks are both split
into `n_shards` contiguous ranges, and a slot only ever receives blocks
homed on its own shard (per-shard free lists). Every device-side index a
slot's table row can carry therefore resolves inside that slot's shard of
the pool, which is what lets the engine run the decode step under a manual
`shard_map` over "data" with NO cross-shard pool traffic — the gather /
scatter that a generically data-sharded pool plus replicated table turns
into a full pool all-gather per step (priced by launch/dryrun decode cells)
stays shard-local. `table_device()` then emits SHARD-LOCAL physical indices
(global id minus the slot's shard base; sentinel -> blocks_per_shard), so
the same gather/scatter primitives work unchanged on the shard-local leaves
shard_map hands them.

Blocks are REFCOUNTED: a physical block may back the same logical prefix of
several sequences at once (serve/prefix_cache.py aliases a cached prefix's
blocks read-only into a new slot's table — `adopt_prefix` — and copies the
first divergent / partial tail block privately — `cow_block`). A block
returns to the free list only when its last reference drops (`_decref`);
`truncate` is logical-only and never frees, so speculative rollback can
never free a block another slot still references. Aliased table entries are
masked out of the WRITE view (`tables_device` stacks a read table and a
write table whose shared-prefix entries hold the sentinel), so a scatter
through them provably drops — docs/CONVENTIONS.md §5.

The device-side primitives (`gather_view` / `scatter_tokens`) are called
from the mixer decode paths (models/attention.py, models/mla.py) on the
stacked leaves at a layer index, in place (docs/CONVENTIONS.md §10); the
`KVPool` class is the host-side allocator driven by the engine scheduler.

With `paged=False` the pool builds dense per-slot caches (n_slots, max_len,
...) instead — same masking conventions, bit-identical attention arithmetic —
used as the reference layout in tests and by the legacy greedy loop.

With `quantized=True` (paged only) every token-kind leaf is stored as NVFP4
bytes instead of bf16 — packed e2m1 codes + e4m3 group scales, 0.28125x the
HBM bytes; a gqa / lattn layer's K and V together as one `PackedKVPair`,
each mla latent leaf as a `PackedKV` — quantized per token at scatter time with
deterministic RTN and dequantized either in the Pallas flash-decode kernel
(kernels/paged_attention.py `*_q` entry points) or exactly in bf16 on the
gather path. The bf16 pool remains the bitwise reference mode; quantized
pools trade bit-exactness for bandwidth under an MSE-tested rounding scheme
(docs/CONVENTIONS.md §7, serve/README.md "Quantized KV cache").
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import formats as F
from repro.kernels import ops as KOPS
from repro.models import griffin as G
from repro.models import lm

TOKEN_KINDS = ("kv", "mla")
STATE_KINDS = ("wkv", "tm_prev", "cm_prev", "lru")
_TOKEN_MIXERS = ("gqa", "lattn", "mla")


class PackedKV(NamedTuple):
    """One NVFP4-quantized token-kind pool leaf (`KVPool(quantized=True)`).

    Two uint8 arrays sharing the leading (pool block, block offset) axes of
    the bf16 leaf they replace: e2m1 codes packed two per byte over the
    LAST feature axis (`..., d/2`) and e4m3 group scales stored as raw bits
    (`..., d/16`) — 0.5625 bytes per cached element vs 2 for bf16.

    A NamedTuple, hence a pytree: `jax.tree.map` descends into both leaves,
    so the jitted block copy (`KVPool._copy_block_device`), the shard_map
    in_specs of serve/decode.py, and `init_cache`'s stage broadcast all
    handle codes and scales together with no special casing — a COW copy
    moves a packed block atomically because both leaves sit in one jitted
    `jax.tree.map`. Quantization is per-token deterministic RTN
    (`core/formats.py:nvfp4_cache_encode`): a token's packed bytes are a
    pure function of its bf16 value, so a block is immutable packed bytes
    once its positions are written, and prefix-cache aliasing / COW reuse
    packed bytes bit-for-bit (hot == cold, docs/CONVENTIONS.md §7).
    """

    codes: jax.Array   # uint8, (..., d // 2): packed e2m1 pairs
    scales: jax.Array  # uint8, (..., d // GROUP): e4m3 scale bits


class PackedKVPair(NamedTuple):
    """The K and V pools of one gqa / lattn layer, NVFP4-quantized TOGETHER
    (`KVPool(quantized=True)`): the same bytes and the same per-token codec
    as two `PackedKV` leaves, laid out so that no leaf ends in an axis of
    hd/2 or hd/16 bytes.

    A TPU stores an array whose last axis is not a whole number of
    128-lane rows with the pool-block axis minor (that way it pads
    nothing), while the paged kernel's operands are row-major: each
    layer's read would relayout the pool. With a head dim that is a
    multiple of 128 both leaves below are whole lane rows, the chip keeps
    them row-major, and the serving step's layer scan reads and writes the
    stacked pool in place (docs/CONVENTIONS.md §10).

      codes:  (..., BS, KV, hd) — per token and head, K's packed e2m1
              pairs (hd/2 bytes), then V's;
      scales: (..., KV, 2 * BS * hd/16) — per head, the e4m3 scale bits of
              the block's BS tokens for K (token t's hd/16 groups at lanes
              t*hd/16 ...), then the same for V.
    """

    codes: jax.Array   # uint8, (..., BS, KV, hd): K pairs | V pairs
    scales: jax.Array  # uint8, (..., KV, 2*BS*hd/16): K bits | V bits


def reclaim_window(cfg: ArchConfig, specs=None) -> int | None:
    """Sliding window W when EVERY token-cache layer in `specs` is `lattn`.

    One block table serves every layer, so a block may only return to the
    free list mid-sequence when NO layer can ever read it again — true
    exactly when all token-cache mixers share the same sliding window
    (recurrent kinds keep O(1) slot state and own no blocks). Mixed stacks
    (any full-attention gqa/mla layer) return None: those layers attend the
    whole prefix forever."""
    specs = specs if specs is not None else lm.layer_specs(cfg)
    mixers = {m for pattern, _ in specs for m, _ in pattern
              if m in _TOKEN_MIXERS}
    if mixers == {"lattn"} and cfg.griffin is not None:
        return cfg.griffin.window
    return None


# --------------------------------------------------------------------------
# device-side primitives (used inside the jitted decode step)
# --------------------------------------------------------------------------

def gather_view(pool, table: jax.Array, layer=None) -> jax.Array:
    """Materialize per-sequence logical views from the pool.

    pool: (P, BS, ...), or a stacked (L, P, BS, ...) leaf read at `layer`;
    table: (B, MAXB) with OOB sentinel for unallocated.
    Returns (B, MAXB*BS, ...): each row's blocks in logical order, zeros for
    unallocated blocks (always masked downstream — attention only admits
    key positions <= the row's current position).

    A `PackedKV` pool gathers both packed leaves and DEQUANTIZES to bf16
    (exact: e2m1 x e4m3 products fit bf16), so the dense/gather attention
    path consumes quantized pools with no mixer changes; unallocated blocks
    decode to exactly 0.0 (zero code x zero scale), preserving the fill
    convention. A `PackedKVPair` gathers the same way into the (K, V)
    pair of views.
    """
    if isinstance(pool, PackedKVPair):
        return _gather_pair(pool, table, layer)
    if isinstance(pool, PackedKV):
        return F.nvfp4_cache_decode(gather_view(pool.codes, table, layer),
                                    gather_view(pool.scales, table, layer))
    if layer is not None:
        pool = jax.lax.dynamic_index_in_dim(pool, layer, keepdims=False)
    v = pool.at[table].get(mode="fill", fill_value=0)
    b, mb = table.shape
    return v.reshape(b, mb * pool.shape[1], *pool.shape[2:])


def pack_pair(k: jax.Array, v: jax.Array) -> PackedKVPair:
    """Quantize bf16 K and V pool blocks (..., BS, KV, hd) into one
    `PackedKVPair` (the per-token codec of `scatter_tokens`)."""
    (ck, sk), (cv, sv) = F.nvfp4_cache_encode(k), F.nvfp4_cache_encode(v)
    *lead, bs, kv, g = sk.shape
    sc = jnp.stack([sk, sv], axis=-4)                    # (..., 2, BS, KV, G)
    sc = jnp.moveaxis(sc, -2, -4).reshape(*lead, kv, 2 * bs * g)
    return PackedKVPair(jnp.concatenate([ck, cv], axis=-1), sc)


def unpack_pair(pool: PackedKVPair) -> tuple[jax.Array, jax.Array]:
    """Inverse of `pack_pair`: the bf16 (K, V) blocks (..., BS, KV, hd),
    exact (e2m1 x e4m3 products fit bf16)."""
    *lead, bs, kv, hd = pool.codes.shape
    g = pool.scales.shape[-1] // (2 * bs)
    sc = pool.scales.reshape(*lead, kv, 2, bs, g)
    sc = jnp.moveaxis(sc, -4, -2)                        # (..., 2, BS, KV, G)
    return (F.nvfp4_cache_decode(pool.codes[..., :hd // 2], sc[..., 0, :, :, :]),
            F.nvfp4_cache_decode(pool.codes[..., hd // 2:], sc[..., 1, :, :, :]))


def _gather_pair(pool: PackedKVPair, table: jax.Array, layer=None):
    """`gather_view` of a `PackedKVPair`: the (K, V) bf16 views."""
    if layer is not None:
        pool = PackedKVPair(*(jax.lax.dynamic_index_in_dim(a, layer,
                                                           keepdims=False)
                              for a in pool))
    b, mb = table.shape
    blocks = PackedKVPair(*(a.at[table].get(mode="fill", fill_value=0)
                            for a in pool))             # (B, MAXB, ...)
    return tuple(x.reshape(b, mb * x.shape[2], *x.shape[3:])
                 for x in unpack_pair(blocks))


def split_tables(block_table: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Resolve a block-table argument into (read_table, write_table).

    A plain (B, MAXB) table is its own write view (the pre-prefix-cache
    layout: dryrun lowering, the speculative draft pool). A stacked
    (B, 2, MAXB) table — `KVPool.tables_device()` — carries a distinct
    write view whose ALIASED-prefix entries hold the OOB sentinel, so
    scatters through shared (refcount > 1) blocks drop by construction
    while gathers still read them."""
    if block_table.ndim == 3:
        return block_table[:, 0], block_table[:, 1]
    return block_table, block_table


def scatter_tokens(pool, table: jax.Array, positions: jax.Array,
                   vals: jax.Array, valid: jax.Array, layer=None):
    """Write per-token values through the block table.

    pool: (P, BS, ...), or a stacked (L, P, BS, ...) leaf written at
    `pool.at[layer, blk, off]` — in place in the serving step's layer scan,
    which carries the whole stacked pool (docs/CONVENTIONS.md §10);
    positions: (B, S) absolute token positions; vals: (B, S, ...);
    valid: (B, S) bool — rows/positions with valid=False (inactive slots,
    out-of-range positions) are routed to the OOB sentinel and dropped.
    NEGATIVE positions are folded into `valid` here: the block lookup
    clips them to 0, so a caller passing valid=True for a not-yet-started
    row (position -1) would otherwise silently corrupt block 0 / offset 0
    — bad positions must route to the sentinel like every other invalid
    write, whatever the caller's mask says.

    A `PackedKV` pool quantizes per token (NVFP4 deterministic RTN over the
    last feature axis) and scatters codes and scale bits through the same
    block/offset indices — per-token groups make each position's packed
    bytes independent, so no block-level staging is needed and a block is
    immutable packed bytes as soon as its positions are written. A
    `PackedKVPair` takes `vals` = (K, V) and writes both (`_scatter_pair`).

    On a mesh the written values take the pool's placement first
    (`kernels.ops.replicated`).
    """
    vals = jax.tree.map(KOPS.replicated, vals)   # the pool's placement
    if isinstance(pool, PackedKVPair):
        return _scatter_pair(pool, table, positions, vals, valid, layer)
    if isinstance(pool, PackedKV):
        codes, scales = F.nvfp4_cache_encode(vals)
        return PackedKV(
            scatter_tokens(pool.codes, table, positions, codes, valid, layer),
            scatter_tokens(pool.scales, table, positions, scales, valid,
                           layer))
    lead = () if layer is None else (layer,)
    blk, off = _block_offsets(pool.shape[len(lead):], table, positions,
                              valid)
    return pool.at[(*lead, blk, off)].set(vals.astype(pool.dtype),
                                          mode="drop")


def _block_offsets(shape, table, positions, valid):
    """(physical block, offset) of each (B, S) position in a pool of one
    layer's `shape` (P, BS, ...); invalid writes get the sentinel P."""
    n_blocks, bs = shape[0], shape[1]
    valid = valid & (positions >= 0)
    logical = jnp.clip(positions, 0) // bs
    blk = table.at[jnp.arange(table.shape[0])[:, None], logical].get(
        mode="fill", fill_value=n_blocks)
    blk = jnp.where(valid, blk, n_blocks)  # OOB => scatter drops
    return blk, jnp.clip(positions, 0) % bs


def _scatter_pair(pool: PackedKVPair, table, positions, vals, valid,
                  layer=None) -> PackedKVPair:
    """`scatter_tokens` of `vals` = (K, V), each (B, S, KV, hd), into a
    `PackedKVPair`: a token's codes are one (KV, hd) row of the codes
    leaf, and its K and V scale bits two (KV, hd/16) windows of each
    head's lane row in the scales leaf."""
    (ck, sk), (cv, sv) = (F.nvfp4_cache_encode(x) for x in vals)
    codes = scatter_tokens(pool.codes, table, positions,
                           jnp.concatenate([ck, cv], axis=-1), valid, layer)
    lead = () if layer is None else (layer,)
    blk, off = _block_offsets(pool.codes.shape[len(lead):], table,
                              positions, valid)
    g, half = sk.shape[-1], pool.scales.shape[-1] // 2
    kv = sk.shape[2]
    # (B, S, 2, KV, G) element indices
    lane = (jnp.arange(2)[:, None, None] * half
            + jnp.arange(g)[None, None, :])[None, None] \
        + (off * g)[:, :, None, None, None]
    head = jnp.arange(kv)[None, None, None, :, None]
    blk5 = blk[:, :, None, None, None]
    scales = pool.scales.at[(*lead, blk5, head, lane)].set(
        jnp.stack([sk, sv], axis=2), mode="drop")
    return PackedKVPair(codes, scales)


# --------------------------------------------------------------------------
# cache construction (stage-aligned, mirrors lm.init_cache layouts)
# --------------------------------------------------------------------------

def _layer_cache(spec, cfg: ArchConfig, n_slots: int, max_len: int, *,
                 paged: bool, n_blocks: int, block_size: int,
                 quantized: bool = False):
    mixer, ff = spec
    hd = cfg.hd
    c: dict[str, Any] = {}

    def check_group(d):
        if d % F.GROUP:
            raise ValueError(
                f"quantized KV pool needs feature dims divisible by "
                f"{F.GROUP} (got {d} for mixer '{mixer}'): NVFP4 groups "
                "lie along the last cache axis")

    def tok(*feat):
        if paged and quantized:
            d = feat[-1]
            check_group(d)
            # zero codes x zero scale bits decode to exactly 0.0, matching
            # the bf16 pool's zero init / gather-fill convention
            return PackedKV(
                jnp.zeros((n_blocks, block_size, *feat[:-1], d // 2),
                          jnp.uint8),
                jnp.zeros((n_blocks, block_size, *feat[:-1], d // F.GROUP),
                          jnp.uint8))
        if paged:
            return jnp.zeros((n_blocks, block_size, *feat), jnp.bfloat16)
        # dense serving cache: full max_len capacity for every kind — the
        # sliding-window ring optimization is a paged-pool follow-on, and a
        # uniform layout keeps dense/paged outputs bit-comparable.
        return jnp.zeros((n_slots, max_len, *feat), jnp.bfloat16)

    if mixer in ("gqa", "lattn") and paged and quantized:
        check_group(hd)
        kv = cfg.n_kv_heads
        c["kv"] = PackedKVPair(
            jnp.zeros((n_blocks, block_size, kv, hd), jnp.uint8),
            jnp.zeros((n_blocks, kv, 2 * block_size * hd // F.GROUP),
                      jnp.uint8))
    elif mixer in ("gqa", "lattn"):
        c["kv"] = (tok(cfg.n_kv_heads, hd), tok(cfg.n_kv_heads, hd))
    elif mixer == "mla":
        m = cfg.mla
        c["mla"] = (tok(m.kv_lora_rank), tok(m.qk_rope_head_dim))
    elif mixer == "rwkv_tm":
        h = cfg.d_model // cfg.rwkv.head_dim
        c["wkv"] = jnp.zeros((n_slots, h, cfg.rwkv.head_dim, cfg.rwkv.head_dim),
                             jnp.float32)
        c["tm_prev"] = jnp.zeros((n_slots, 1, cfg.d_model), jnp.bfloat16)
    elif mixer == "rec":
        c["lru"] = G.recurrent_state_init(cfg, n_slots)
    if ff == "rwkv_cm":
        c["cm_prev"] = jnp.zeros((n_slots, 1, cfg.d_model), jnp.bfloat16)
    return c


def init_cache(cfg: ArchConfig, n_slots: int, max_len: int, *, paged: bool,
               n_blocks: int, block_size: int, specs=None,
               quantized: bool = False):
    """Stage-aligned serving cache pytree (pool layout when paged).

    `specs` overrides lm.layer_specs(cfg) — used by the speculative DRAFT
    pool, whose cache covers only lm.prefix_specs(cfg, draft_layers).
    `quantized` stores token kinds as NVFP4 bytes: `PackedKVPair` for
    gqa / lattn layers, a `PackedKV` per leaf for mla."""
    stages = []
    for pattern, count in (specs if specs is not None else lm.layer_specs(cfg)):
        one = {f"l{i}": _layer_cache(pattern[i], cfg, n_slots, max_len,
                                     paged=paged, n_blocks=n_blocks,
                                     block_size=block_size,
                                     quantized=quantized)
               for i in range(len(pattern))}
        stages.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (count, *x.shape)), one))
    return stages


def _map_token_kinds(caches, fn):
    """Apply fn to every token-kind leaf (kv / mla pool arrays)."""
    out = []
    for stage in caches:
        ns = {}
        for lk, kinds in stage.items():
            ns[lk] = {k: (jax.tree.map(fn, v) if k in TOKEN_KINDS else v)
                      for k, v in kinds.items()}
        out.append(ns)
    return out


def _map_state_kinds(caches, fn):
    """Apply fn to every state-kind entry (list[stage] -> dict[l] -> kinds)."""
    out = []
    for stage in caches:
        ns = {}
        for lk, kinds in stage.items():
            ns[lk] = {k: (jax.tree.map(fn, v) if k in STATE_KINDS else v)
                      for k, v in kinds.items()}
        out.append(ns)
    return out


# --------------------------------------------------------------------------
# host-side allocator
# --------------------------------------------------------------------------

class OutOfBlocks(RuntimeError):
    pass


class SlotError(RuntimeError):
    """Allocator misuse: double-free, or operating on an unbound slot."""


class KVPool:
    """Host-side block allocator + owner of the device cache pytree.

    Slot lifecycle: `reset_slot(slot)` (zero recurrent state of an UNBOUND
    slot) -> `commit(slot, total)` (bind + reserve growth) -> `ensure(slot,
    n)` before each forward so every position < n has a backing block ->
    optionally `truncate(slot, n)` (speculative rollback: logical shrink,
    no block churn) -> `release(slot)` (unbind; blocks return to the free
    list). Misuse — releasing an unbound slot (double-free), committing a
    bound slot, ensure/truncate outside a binding — raises SlotError rather
    than silently corrupting the free-list accounting. Token blocks are
    never zeroed: stale values sit behind the position mask.

    Pure sliding-window stacks (`reclaim_window`) additionally free blocks
    mid-sequence once they fall out of every future query's window (`ensure`
    runs `_reclaim` before growing), keeping live blocks O(window) per slot;
    a truncate below the reclaim floor raises SlotError because the rolled-
    back window would need keys that no longer exist.

    `n_shards > 1` makes allocation SLOT-AFFINE for mesh-sharded serving:
    shard s owns slots [s*n_slots/S, (s+1)*n_slots/S) and physical blocks
    [s*n_blocks/S, (s+1)*n_blocks/S), each shard runs its own free list, and
    a slot allocates exclusively from its shard. Admission becomes per-shard
    (`can_admit(..., slot=i)`) — one hot shard can be full while another has
    room. Single-shard behavior (the default) is bit-for-bit unchanged.
    """

    def __init__(self, cfg: ArchConfig, n_slots: int, max_len: int, *,
                 paged: bool = True, block_size: int = 16,
                 n_blocks: int | None = None, specs=None, n_shards: int = 1,
                 quantized: bool = False):
        assert max_len % block_size == 0, \
            f"max_len {max_len} must be a multiple of block_size {block_size}"
        if quantized and not paged:
            raise ValueError(
                "quantized=True requires paged=True: the NVFP4 cache format "
                "is a property of pool blocks (dense mode is the bitwise "
                "reference layout and stays bf16)")
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.paged = paged
        self.quantized = quantized
        self.block_size = block_size
        self.max_blocks = max_len // block_size
        if n_blocks is None:
            n_blocks = n_slots * self.max_blocks
        if n_shards < 1 or n_slots % n_shards or n_blocks % n_shards:
            raise ValueError(
                f"n_shards={n_shards} must divide both n_slots={n_slots} and "
                f"n_blocks={n_blocks} (equal shard extents are what keep the "
                "shard_map slot split aligned with the block homes)")
        self.n_blocks = n_blocks
        self.sentinel = n_blocks
        self.n_shards = n_shards
        self.slots_per_shard = n_slots // n_shards
        self.blocks_per_shard = n_blocks // n_shards
        self.specs = specs if specs is not None else lm.layer_specs(cfg)
        self.caches = init_cache(cfg, n_slots, max_len, paged=paged,
                                 n_blocks=n_blocks, block_size=block_size,
                                 specs=self.specs, quantized=quantized)
        self.has_state_kinds = any(
            mixer in ("rwkv_tm", "rec") or ff == "rwkv_cm"
            for pattern, _ in self.specs for mixer, ff in pattern)
        self._table = np.full((n_slots, self.max_blocks), self.sentinel,
                              np.int32)
        # per-shard free lists; pop() -> the shard's lowest block id first
        # (n_shards=1: one list over all blocks, the original behavior)
        bps = self.blocks_per_shard
        self._frees: list[list[int]] = [
            list(range((s + 1) * bps - 1, s * bps - 1, -1))
            for s in range(n_shards)]
        self._owned: list[list[int]] = [[] for _ in range(n_slots)]
        self._committed = [0] * n_slots  # reserved blocks per admitted seq
        self._bound = [False] * n_slots  # slot currently holds a sequence
        self._lengths = [0] * n_slots    # logical tokens backed per slot
        # per-block reference counts: slot table rows referencing the block
        # plus at most one prefix-cache hold (serve/prefix_cache.py). A block
        # is free iff its refcount is 0; _decref is the ONLY path back to the
        # free list, so a shared block can never be double-freed.
        self._ref = np.zeros(n_blocks, np.int32)
        # leading logical blocks of each slot that are ALIASED (read-only):
        # the write view of tables_device() masks them with the sentinel
        self._shared_upto = [0] * n_slots
        # set by the prefix cache: callable(shard, need) -> blocks freed into
        # that shard's list by evicting unpinned cached prefixes
        self.evict_hook = None
        # observability hook (obs/instrumentation.py Instrumentation), set
        # by the engine when EngineConfig(obs=...) is enabled; None costs
        # one `is not None` test per allocation event
        self.obs = None
        self._table_dev = None
        self._tables_dev = None
        self._copy_fn = None
        self._overflow_fn = None
        self._read_block_fn = None
        self._write_block_fn = None
        # sliding-window reclamation (pure-lattn stacks, paged mode only):
        # blocks whose newest key predates every future query's window go
        # back to the free list mid-sequence, so live blocks per slot stay
        # O(window) instead of O(sequence length)
        self.window = reclaim_window(cfg, self.specs) if paged else None
        self._alloc_upto = [0] * n_slots   # logical blocks ever allocated
        self._live_from = [0] * n_slots    # first logical block still owned
        self._floor = [0] * n_slots        # min sound truncate target

    # ---- block accounting ----

    def shard_of_slot(self, slot: int) -> int:
        """Mesh-"data" shard homing `slot` (contiguous split, matching how
        shard_map splits the leading slot/block axes of the device arrays)."""
        return slot // self.slots_per_shard

    def shard_of_block(self, block: int) -> int:
        return block // self.blocks_per_shard

    @property
    def _free(self) -> list[int]:
        """Flat view of every free block (invariant checks / introspection).

        Allocation goes through the per-shard `_frees` lists; this view keeps
        single-shard callers and the property-test suite working unchanged."""
        return [b for shard in self._frees for b in shard]

    @property
    def free_block_count(self) -> int:
        return sum(len(shard) for shard in self._frees)

    def free_blocks_in_shard(self, shard: int) -> int:
        return len(self._frees[shard])

    def utilization(self) -> dict:
        """Host-side occupancy snapshot for the per-tick gauges
        (obs/instrumentation.py): free blocks per shard, allocated blocks,
        and internal fragmentation — token capacity sitting in allocated
        blocks that no live position occupies (partial tail blocks plus
        window-reclaim slack). Dense pools report zero blocks."""
        if not self.paged:
            return {"free_by_shard": [0] * self.n_shards,
                    "allocated_blocks": 0, "frag_tokens": 0,
                    "frag_ratio": 0.0}
        free = [len(f) for f in self._frees]
        cap = live = 0
        for i in range(self.n_slots):
            if not self._bound[i]:
                continue
            cap += len(self._owned[i]) * self.block_size
            live += self._lengths[i] - self._live_from[i] * self.block_size
        frag = max(cap - live, 0)
        return {"free_by_shard": free,
                "allocated_blocks": self.n_blocks - sum(free),
                "frag_tokens": frag,
                "frag_ratio": frag / cap if cap else 0.0}

    def effective_free_blocks(self, shard: int) -> int:
        """Free blocks of `shard` minus outstanding commitments of its
        admitted slots — the capacity a NEW request could actually draw on.
        The engine's shard-occupancy placement ranks shards by this, so a
        freshly committed (not yet allocated) sequence already steers the
        next admission elsewhere."""
        if self.n_shards == 1:
            shard_slots = range(self.n_slots)
        else:
            shard_slots = range(shard * self.slots_per_shard,
                                (shard + 1) * self.slots_per_shard)
        outstanding = sum(self._committed[i] - len(self._owned[i])
                          for i in shard_slots)
        return len(self._frees[shard]) - outstanding

    def blocks_for(self, n_tokens: int) -> int:
        return math.ceil(n_tokens / self.block_size)

    def max_live_blocks(self, total_tokens: int,
                        max_growth: int | None = None) -> int:
        """Most blocks a sequence of total_tokens can own SIMULTANEOUSLY.

        Without a reclaim window this is just blocks_for(total). With one,
        `ensure` reclaims before every growth step, so — provided no single
        ensure grows a slot by more than `max_growth` tokens — a slot spans
        at most window + one growth chunk of live positions (plus block-
        granularity slack at both ends). This is what makes long sequences
        admissible to pools far smaller than blocks_for(total): the whole
        point of mid-sequence reclamation."""
        need = self.blocks_for(total_tokens)
        if self.window is None or max_growth is None:
            return need
        return min(need, self.blocks_for(self.window + max_growth) + 2)

    def can_ever_admit(self, total_tokens: int,
                       max_growth: int | None = None) -> bool:
        """Is a sequence of total_tokens servable by this pool at all?

        Slot-affine pools bound a single sequence by ONE SHARD's blocks — a
        slot can never borrow from another shard's free list."""
        if total_tokens > self.max_len:
            return False
        return (not self.paged) or (
            self.max_live_blocks(total_tokens, max_growth)
            <= self.blocks_per_shard)

    def can_admit(self, total_tokens: int, max_growth: int | None = None,
                  slot: int | None = None, cached_blocks: int = 0) -> bool:
        """Admission check: can a sequence of total_tokens be fully served
        alongside every already-admitted sequence?

        Blocks are allocated lazily (`ensure`), so the check subtracts the
        outstanding COMMITMENTS of admitted sequences (reserved via
        `commit`, not yet allocated) — otherwise two growing sequences could
        both pass admission and later exhaust the pool mid-decode. With
        `n_shards > 1` pass the candidate `slot`: only its shard's free
        blocks and commitments count (slot affinity makes shards independent
        allocators). `cached_blocks` — prefix-cache blocks the candidate
        would ADOPT rather than allocate (they are already resident, outside
        the free list) — reduces its demand on the free list."""
        if total_tokens > self.max_len:
            return False
        if not self.paged:
            return True
        if self.n_shards > 1 and slot is None:
            # no target slot: "can ANY shard take it" — never whole-pool
            # accounting, which would over-admit (global free blocks can
            # span shards no single slot may draw from)
            return any(self.can_admit(total_tokens, max_growth,
                                      slot=sh * self.slots_per_shard)
                       for sh in range(self.n_shards))
        if self.n_shards == 1:
            shard_slots = range(self.n_slots)
            free = self.free_block_count
        else:
            sh = self.shard_of_slot(slot)
            shard_slots = range(sh * self.slots_per_shard,
                                (sh + 1) * self.slots_per_shard)
            free = self.free_blocks_in_shard(sh)
        outstanding = sum(self._committed[i] - len(self._owned[i])
                          for i in shard_slots)
        need = max(0, self.max_live_blocks(total_tokens, max_growth)
                   - cached_blocks)
        return free - outstanding >= need

    def admission_shortfall(self, total_tokens: int,
                            max_growth: int | None = None,
                            slot: int | None = None,
                            cached_blocks: int = 0) -> int:
        """Free blocks MISSING for `can_admit` to pass on `slot`'s shard
        (0 when it already passes) — what the engine asks the prefix cache
        to evict before admitting."""
        if not self.paged or total_tokens > self.max_len:
            return 0
        if self.n_shards == 1:
            shard_slots = range(self.n_slots)
            free = self.free_block_count
        else:
            sh = self.shard_of_slot(slot)
            shard_slots = range(sh * self.slots_per_shard,
                                (sh + 1) * self.slots_per_shard)
            free = self.free_blocks_in_shard(sh)
        outstanding = sum(self._committed[i] - len(self._owned[i])
                          for i in shard_slots)
        need = max(0, self.max_live_blocks(total_tokens, max_growth)
                   - cached_blocks)
        return max(0, need - (free - outstanding))

    def commit(self, slot: int, total_tokens: int,
               max_growth: int | None = None) -> None:
        """Bind `slot` and reserve (without allocating) its growth blocks.

        `max_growth` — the caller's bound on tokens added per `ensure`
        (the engine's max(prefill_chunk, spec_k + 1)) — caps the
        reservation of window-reclaimed slots at their live-block bound."""
        if self._bound[slot]:
            raise SlotError(f"slot {slot}: commit on a bound slot "
                            "(release it first)")
        if total_tokens > self.max_len:
            raise OutOfBlocks(f"slot {slot}: {total_tokens} > max_len")
        self._bound[slot] = True
        self._committed[slot] = self.max_live_blocks(total_tokens, max_growth)

    def ensure(self, slot: int, n_tokens: int) -> None:
        """Allocate blocks so positions [0, n_tokens) of `slot` are backed."""
        if not self._bound[slot]:
            raise SlotError(f"slot {slot}: ensure on an unbound slot")
        if not self.paged:
            if n_tokens > self.max_len:
                raise OutOfBlocks(f"slot {slot}: {n_tokens} > max_len")
            self._lengths[slot] = max(self._lengths[slot], n_tokens)
            return
        need = self.blocks_for(n_tokens)
        owned = self._owned[slot]
        if need > self.max_blocks:
            raise OutOfBlocks(f"slot {slot}: {n_tokens} tokens exceed the "
                              f"{self.max_blocks}-entry block table")
        if self.window is not None:
            self._reclaim(slot)
        sh = self.shard_of_slot(slot)
        free = self._frees[sh]
        taken = 0
        while self._alloc_upto[slot] < need:
            if not free and not (self.evict_hook is not None
                                 and self.evict_hook(sh, 1) > 0):
                raise OutOfBlocks(
                    f"slot {slot}: pool exhausted"
                    + (f" (shard {sh})" if self.n_shards > 1 else ""))
            blk = free.pop()
            self._ref[blk] = 1
            self._table[slot, self._alloc_upto[slot]] = blk
            owned.append(blk)
            self._alloc_upto[slot] += 1
            taken += 1
            self._dirty()
        if taken and self.obs is not None:
            self.obs.on_pool_alloc(taken)
        self._lengths[slot] = max(self._lengths[slot], n_tokens)

    def _reclaim(self, slot: int) -> None:
        """Return out-of-window blocks of `slot` to the free list.

        Called from `ensure` BEFORE growth, so the basis length is the
        committed prefix: every future query sits at qpos >= cur (truncate
        back below the in-flight chunk lands at >= cur too — spec rollback
        targets the pre-ensure length). Block j (keys [j*BS, (j+1)*BS)) is
        dead once its newest key leaves the oldest such query's window:
        (j+1)*BS - 1 <= cur - window. Freed table entries become the OOB
        sentinel — gathers read zeros and the kernel skips them, both
        behind the window mask, so paged output stays bit-identical."""
        cur = self._lengths[slot]
        first_live = min(max(0, (cur + 1 - self.window) // self.block_size),
                         self._alloc_upto[slot])
        if first_live <= self._live_from[slot]:
            return
        for j in range(self._live_from[slot], first_live):
            blk = int(self._table[slot, j])
            self._table[slot, j] = self.sentinel
            self._owned[slot].remove(blk)
            self._decref(blk)
        if self.obs is not None:
            self.obs.on_pool_reclaim(first_live - self._live_from[slot])
        self._live_from[slot] = first_live
        self._dirty()
        # freed keys end at first_live*BS - 1; a truncate to n keeps windows
        # sound only while n - window >= that newest freed key
        self._floor[slot] = first_live * self.block_size + self.window - 1

    def truncate(self, slot: int, n_tokens: int) -> None:
        """Logically shrink `slot` to n_tokens positions (spec rollback).

        Rejected draft tokens are dropped WITHOUT block churn: the slot keeps
        every block it owns (the very next rounds grow back into them), and
        stale values past n_tokens stay invisible behind the position mask
        until overwritten. Only the logical length moves."""
        if not self._bound[slot]:
            raise SlotError(f"slot {slot}: truncate on an unbound slot")
        if n_tokens < 0 or n_tokens > self._lengths[slot]:
            raise SlotError(
                f"slot {slot}: truncate to {n_tokens} outside "
                f"[0, {self._lengths[slot]}]")
        if n_tokens < self._floor[slot]:
            # sliding-window reclamation already freed keys the rolled-back
            # window would need; allowing this would silently read zeros
            raise SlotError(
                f"slot {slot}: truncate to {n_tokens} below the "
                f"window-reclaim floor {self._floor[slot]}")
        self._lengths[slot] = n_tokens

    def length(self, slot: int) -> int:
        """Logical backed length of `slot` (ensure grows it, truncate cuts)."""
        return self._lengths[slot]

    def release(self, slot: int) -> None:
        """Unbind `slot`, dropping its block references.

        Exclusively-owned blocks (refcount 1) return to the free list;
        blocks the prefix cache (or another slot) still references merely
        lose this slot's reference — never a double free."""
        if not self._bound[slot]:
            raise SlotError(f"slot {slot}: release on an unbound slot "
                            "(double-free?)")
        self._bound[slot] = False
        self._committed[slot] = 0
        self._lengths[slot] = 0
        if not self.paged:
            return
        blocks = self._owned[slot]
        if blocks:
            # slot affinity: every owned block homes on the slot's shard;
            # reversed so an exclusive slot's blocks re-enter the free list
            # in the pre-refcount order (first-allocated pops first)
            for blk in reversed(blocks):
                self._decref(blk)
            self._owned[slot] = []
        if self._alloc_upto[slot]:
            self._table[slot, :] = self.sentinel
            self._dirty()
        self._alloc_upto[slot] = 0
        self._live_from[slot] = 0
        self._floor[slot] = 0
        self._shared_upto[slot] = 0

    def _dirty(self) -> None:
        """Invalidate the cached device tables after any host-table edit."""
        self._table_dev = None
        self._tables_dev = None

    def _local_table_np(self) -> np.ndarray:
        """Host copy of the device-facing table (shard-local when sharded).

        Slot-affine pools emit SHARD-LOCAL physical indices: the decode step
        runs under a manual shard_map over "data", so each shard's rows must
        index its own (n_blocks/S)-block slice of the pool. Real entries
        subtract the slot's shard base; sentinels map to the LOCAL sentinel
        `blocks_per_shard` (still OOB-high for the local leaves — scatter
        drops, gathers fill zeros, exactly as in the single-shard layout)."""
        if self.n_shards == 1:
            return self._table.copy()
        base = (np.arange(self.n_slots, dtype=np.int32)
                // self.slots_per_shard)[:, None] * self.blocks_per_shard
        return np.where(self._table == self.sentinel,
                        self.blocks_per_shard,
                        self._table - base).astype(np.int32)

    @property
    def local_sentinel(self) -> int:
        return self.blocks_per_shard if self.n_shards > 1 else self.sentinel

    def table_device(self):
        """Device copy of the block table (None in dense mode)."""
        if not self.paged:
            return None
        if self._table_dev is None:
            self._table_dev = jnp.asarray(self._local_table_np())
        return self._table_dev

    @property
    def tables_stale(self) -> bool:
        """True when the next `tables_device()` call rebuilds the tables
        and uploads them (paged pools whose tables changed since)."""
        return self.paged and self._tables_dev is None

    def tables_device(self):
        """Stacked (n_slots, 2, max_blocks) device tables (None when dense):
        [:, 0] the READ table, [:, 1] the WRITE table, in which every
        ALIASED logical block (`adopt_prefix`) holds the sentinel. The decode
        step scatters through the write view only (`split_tables` in the
        mixers), so shared prefix blocks are provably never written — the
        masking is in the data, not in a host-side argument the compiled
        step could ignore."""
        if not self.paged:
            return None
        if self._tables_dev is None:
            rt = self._local_table_np()
            wt = rt.copy()
            for s, k in enumerate(self._shared_upto):
                if k:
                    wt[s, :k] = self.local_sentinel
            self._tables_dev = jnp.asarray(
                np.stack([rt, wt], axis=1).astype(np.int32))
        return self._tables_dev

    # ---- prefix sharing (refcounted aliasing + copy-on-write) ------------

    def _decref(self, block: int) -> None:
        """Drop one reference; the last reference frees the block."""
        self._ref[block] -= 1
        if self._ref[block] < 0:
            raise SlotError(f"block {block}: decref below zero (double free)")
        if self._ref[block] == 0:
            self._frees[self.shard_of_block(block)].append(block)
            if self.obs is not None:
                self.obs.on_pool_free(1)

    def incref(self, block: int) -> None:
        """Add an external (prefix-cache) hold on an allocated block."""
        if self._ref[block] <= 0:
            raise SlotError(f"block {block}: incref on a free block")
        self._ref[block] += 1

    def refcount(self, block: int) -> int:
        return int(self._ref[block])

    def adopt_prefix(self, slot: int, blocks: list[int],
                     n_tokens: int) -> None:
        """Alias cached `blocks` READ-ONLY as `slot`'s logical prefix.

        The slot's table rows [0, len(blocks)) point at the shared physical
        blocks (each incref'd); the write view of `tables_device()` masks
        them with the sentinel, so the slot can gather the cached K/V but
        any scatter targeting those logical blocks drops. Only valid on a
        freshly committed slot (no blocks yet), with every block homed on
        the slot's shard (the slot-affine invariant the sharded decode step
        rests on), and only for unwindowed pools (a reclaimed prefix is not
        fully resident, so sharing it would read zeros)."""
        if not self.paged:
            raise SlotError("adopt_prefix on a dense pool: no block table")
        if not self._bound[slot]:
            raise SlotError(f"slot {slot}: adopt_prefix on an unbound slot")
        if self._owned[slot]:
            raise SlotError(f"slot {slot}: adopt_prefix after allocation")
        if self.window is not None:
            raise SlotError("adopt_prefix on a sliding-window pool: "
                            "reclaimed prefixes are not fully resident")
        if n_tokens > len(blocks) * self.block_size:
            raise SlotError(f"slot {slot}: {n_tokens} tokens exceed "
                            f"{len(blocks)} adopted blocks")
        sh = self.shard_of_slot(slot)
        if any(self.shard_of_block(b) != sh for b in blocks):
            raise SlotError(
                f"slot {slot} (shard {sh}): adopting off-shard blocks "
                "violates slot affinity")
        for j, blk in enumerate(blocks):
            self.incref(blk)
            self._table[slot, j] = blk
            self._owned[slot].append(blk)
        self._alloc_upto[slot] = len(blocks)
        self._shared_upto[slot] = len(blocks)
        self._lengths[slot] = max(self._lengths[slot], n_tokens)
        self._dirty()

    def cow_block(self, slot: int, src: int) -> int:
        """Copy-on-write: append a PRIVATE copy of block `src` as `slot`'s
        next logical block (the first divergent token or a partial tail
        falls inside a cached block: its contents up to the divergence are
        reused bit-for-bit, the rest is stale-behind-the-position-mask and
        overwritten by subsequent scatters). Returns the new block id."""
        if not self.paged:
            raise SlotError("cow_block on a dense pool: no block table")
        if not self._bound[slot]:
            raise SlotError(f"slot {slot}: cow_block on an unbound slot")
        sh = self.shard_of_slot(slot)
        if self.shard_of_block(src) != sh:
            raise SlotError(f"slot {slot} (shard {sh}): COW source {src} "
                            "homes on another shard")
        if self._ref[src] <= 0:
            raise SlotError(f"block {src}: COW from a free block")
        j = self._alloc_upto[slot]
        if j >= self.max_blocks:
            # checked BEFORE popping: a pop-then-raise would strand the
            # popped block at refcount 1 with no owner (unreachable leak)
            raise OutOfBlocks(f"slot {slot}: table full at COW")
        free = self._frees[sh]
        if not free and not (self.evict_hook is not None
                             and self.evict_hook(sh, 1) > 0):
            raise OutOfBlocks(f"slot {slot}: no free block for COW"
                              + (f" (shard {sh})" if self.n_shards > 1
                                 else ""))
        dst = free.pop()
        self._ref[dst] = 1
        self._table[slot, j] = dst
        self._owned[slot].append(dst)
        self._alloc_upto[slot] = j + 1
        self._copy_block_device(src, dst)
        self._dirty()
        if self.obs is not None:
            self.obs.on_pool_alloc(1)
            self.obs.on_pool_cow()
        return dst

    def _copy_block_device(self, src: int, dst: int) -> None:
        """Device copy of every token-kind leaf's block `src` -> `dst`
        (GLOBAL ids — the cache pytree lives in its committed global
        layout; the per-step shard split happens inside the jitted step).

        Multi-leaf token kinds copy ATOMICALLY: `_map_token_kinds` applies
        the copy via `jax.tree.map`, and a quantized pool's `PackedKV` is a
        NamedTuple pytree, so its codes AND scale leaves move in the same
        jitted call — a COW'd packed block can never pair fresh codes with
        stale scales (tests/test_kv_quant.py pins the round trip)."""
        if self._copy_fn is None:
            def cp(caches, s, d):
                return _map_token_kinds(
                    caches, lambda leaf: leaf.at[:, d].set(leaf[:, s]))
            self._copy_fn = jax.jit(cp, donate_argnums=(0,))
        self.caches = self._copy_fn(self.caches, jnp.int32(src),
                                    jnp.int32(dst))

    # ---- host spill tier (hierarchical prefix cache) ---------------------
    #
    # The prefix cache's host-RAM tier (serve/prefix_cache.py) stores
    # evicted blocks as IMMUTABLE host snapshots of the device bytes:
    # PackedKV pools round-trip their packed uint8 codes + scales verbatim,
    # bf16 pools round-trip bf16 — either way host->device->host is the
    # identity, which is what makes a spill-hot stream bitwise-equal to
    # cold (docs/CONVENTIONS.md §9). Only the engine thread calls these.

    def read_block_host(self, block: int):
        """Snapshot every token-kind leaf's block `block` to host memory.

        Returns `(payload, nbytes)`: a pytree of numpy arrays mirroring the
        token-kind structure of `self.caches` (PackedKV stays a PackedKV of
        uint8 arrays — packed bytes, never dequantized), plus its host
        footprint. Synchronous (one device_get), so it is an eviction-path
        facility, never called from compiled code."""
        if not self.paged:
            raise SlotError("read_block_host on a dense pool: no blocks")
        if self._read_block_fn is None:
            def rd(caches, b):
                out = []
                for stage in caches:
                    ns = {}
                    for lk, kinds in stage.items():
                        tk = {k: jax.tree.map(lambda leaf: leaf[:, b], v)
                              for k, v in kinds.items() if k in TOKEN_KINDS}
                        if tk:
                            ns[lk] = tk
                    out.append(ns)
                return out
            self._read_block_fn = jax.jit(rd)
        payload = jax.device_get(self._read_block_fn(self.caches,
                                                     jnp.int32(block)))
        nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(payload))
        return payload, nbytes

    def write_block_host(self, block: int, payload) -> None:
        """Write a `read_block_host` payload into device block `block`.

        Dispatch-only: the jitted scatter is enqueued WITHOUT blocking, so a
        swap-in overlaps subsequent host work (decode ticks); any later step
        reading the pool sees the write because it consumes the rebound
        `self.caches` pytree — XLA orders the dependency, no host sync is
        ever needed for correctness."""
        if not self.paged:
            raise SlotError("write_block_host on a dense pool: no blocks")
        if self._write_block_fn is None:
            def wr(caches, pay, b):
                out = []
                for stage, ps in zip(caches, pay):
                    ns = {}
                    for lk, kinds in stage.items():
                        pk = ps.get(lk, {})
                        ns[lk] = {
                            k: (jax.tree.map(
                                lambda leaf, p: leaf.at[:, b].set(p), v,
                                pk[k]) if k in TOKEN_KINDS else v)
                            for k, v in kinds.items()}
                    out.append(ns)
                return out
            self._write_block_fn = jax.jit(wr, donate_argnums=(0,))
        self.caches = self._write_block_fn(self.caches, payload,
                                           jnp.int32(block))

    def alloc_cache_block(self, shard: int) -> int:
        """Allocate one block on `shard` OWNED BY THE PREFIX CACHE (ref 1,
        no slot): the target of a host-tier swap-in or a cross-shard
        replication copy. Falls back to `evict_hook` under pressure exactly
        like `ensure`; the caller must pin (acquire) any cache path it is
        materializing FIRST, or the eviction could spill the very nodes the
        swap-in is for."""
        free = self._frees[shard]
        if not free and not (self.evict_hook is not None
                             and self.evict_hook(shard, 1) > 0):
            raise OutOfBlocks(f"shard {shard}: no free block for the cache")
        blk = free.pop()
        self._ref[blk] = 1
        if self.obs is not None:
            self.obs.on_pool_alloc(1)
        return blk

    def check_quant_overflow(self, vals: jax.Array) -> float:
        """Debug-mode overflow detector for the cache-quantization path.

        Replays `nvfp4_cache_encode`'s scale chain on `vals` (anything a
        mixer would scatter into this pool) and returns the fraction of
        normalized magnitudes past the E2M1 edge — the 16/17 scale margin
        pins it to exactly 0.0, and a nonzero value means the silent
        saturation bias `core/formats.py:fp4_sr` documents is active.
        Host-side and synchronous (one device_get), so it is a debug /
        test / probe facility, NEVER called from the jitted step
        (docs/CONVENTIONS.md §6 forbids callbacks in compiled code, which
        is why this check cannot live inside `scatter_tokens` itself)."""
        if not self.quantized:
            return 0.0
        if self._overflow_fn is None:
            self._overflow_fn = jax.jit(F.nvfp4_cache_overflow)
        return float(self._overflow_fn(vals))

    # ---- slot state ----

    def reset_slot(self, slot: int) -> None:
        """Zero the recurrent state of `slot` (new sequence admitted).

        Only valid on an UNBOUND slot: resetting a live sequence's state
        would silently corrupt it, so that is a SlotError."""
        if self._bound[slot]:
            raise SlotError(f"slot {slot}: reset_slot on a bound slot")
        self.caches = _map_state_kinds(
            self.caches, lambda leaf: leaf.at[:, slot].set(0))

    # ---- speculative rollback of recurrent state -------------------------
    #
    # Token kinds truncate for free (position-masked); the recurrent kinds
    # (wkv / tm_prev / cm_prev / lru) integrate every token irreversibly, so
    # rollback is snapshot -> verify chunk -> restore for rejected slots.

    def snapshot_states(self):
        """Copies of every state-kind leaf (None if this arch has none).

        Real device copies, not references: the engine's jitted step donates
        the cache pytree, which invalidates the pre-step buffers."""
        if not self.has_state_kinds:
            return None
        out = []
        for stage in self.caches:
            ns = {}
            for lk, kinds in stage.items():
                sk = {k: v for k, v in kinds.items() if k in STATE_KINDS}
                if sk:
                    ns[lk] = jax.tree.map(lambda x: jnp.array(x, copy=True),
                                          sk)
            out.append(ns)
        return out

    def restore_states(self, snapshot, slots) -> None:
        """Write `slots`' rows of every state-kind leaf back from snapshot."""
        if snapshot is None or not slots:
            return
        idx = np.asarray(list(slots), np.int32)

        def put(cur, snap):
            return cur.at[:, idx].set(snap[:, idx])

        new = []
        for stage, sstage in zip(self.caches, snapshot):
            ns = {}
            for lk, kinds in stage.items():
                ns[lk] = {k: (jax.tree.map(put, v, sstage[lk][k])
                              if k in STATE_KINDS else v)
                          for k, v in kinds.items()}
            new.append(ns)
        self.caches = new
