"""Serving step builders: prefill, decode, and the mesh-sharded decode step.

`make_paged_serve_step` is THE engine step (serve/engine.py compiles it per
chunk size; launch/dryrun lowers it for decode cells), and
`make_sharded_serve_step` is its multi-host form — the same function body
under a `shard_map` manual over "data" only, "model" left to GSPMD
(slot-affine pool slices, shard-local block tables; see serve/README.md
"Multi-host serving").
`make_serve_step`/`greedy_generate` remain as the legacy dense-cache
fixed-batch path (benchmarks' seed baseline, simple examples).

Role-split engines (`EngineConfig.role`) reuse these builders unchanged:
a "prefill" engine compiles only the prefill/chunk steps it runs before
exporting a `Handoff`, a "decode" engine admits handoffs through the
prefix cache and runs the same decode step as a monolithic engine — the
split is pure engine-loop policy, never a third step variant.

Forward quantization (RTN + 4/6) is deterministic, so serving needs no
per-step randomness — the seed below is a fixed constant feeding the
(unused-in-inference) backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import lm

_SEED = jnp.array([7, 7], jnp.uint32)


def make_prefill_step(cfg, scheme: str):
    def prefill_step(params, cache, batch):
        logits, cache, _ = lm.forward(params, cfg, batch, scheme,
                                      jnp.asarray(_SEED), caches=cache,
                                      mode="prefill")
        return logits, cache
    return prefill_step


def make_serve_step(cfg, scheme: str):
    def serve_step(params, cache, tokens, pos):
        logits, cache, _ = lm.forward(params, cfg, {"tokens": tokens}, scheme,
                                      jnp.asarray(_SEED), caches=cache,
                                      mode="decode", pos=pos)
        return logits, cache
    return serve_step


def make_sharded_serve_step(cfg, scheme: str, mesh, *,
                            paged_kernel: bool = False):
    """The engine's decode/prefill/verify step wrapped in a `shard_map` over
    the mesh's "data" axis — the multi-host serving hot path.

    Split of labor (see serve/README.md "Multi-host serving"):

      manual over "data" — decode slots, the KV pool (block axis of token
        kinds, slot axis of state kinds / dense caches), the block table,
        and the per-slot tokens/pos/active inputs all enter pre-split
        (`in_specs` below). The pool allocator is slot-affine
        (`KVPool(n_shards=...)`) and `table_device()` carries SHARD-LOCAL
        physical indices, so every gather/scatter the step performs resolves
        inside the local pool slice: the forward body runs UNCHANGED on
        local shapes, and no collective ever touches the pool.
      GSPMD over every other axis ("model", "pod") — they are not in the
        shard_map's `axis_names`, so `PackedQWeight` leaves placed with
        `dist.sharding.serve_param_shardings` compute row-split GEMMs with
        XLA-inserted activation reductions (activation-sized, not
        pool-sized, wire). The Pallas attention kernels, which GSPMD cannot
        partition, run manual over those axes too (kernels/ops.py).

    Exactness: the decode forward is row-local per slot (docs/CONVENTIONS.md
    records the contract), so with model=1 the emitted greedy streams are
    BITWISE identical to the single-host engine — tests/test_serve_sharded.py
    pins this. check_vma is off: the replication check cannot see through
    the GSPMD axes.
    """
    return shard_serve_step(
        make_paged_serve_step(cfg, scheme, paged_kernel=paged_kernel), mesh)


def shard_serve_step(step, mesh, *, out_batch_axis: int = 0):
    """shard_map-wrap any engine-step-signature function
    `(params, cache, table, tokens, pos, active) -> (out, cache)` with the
    standard serving specs: params replicated over the manual "data" axis
    (every other mesh axis left to GSPMD), cache leaves split on axis 1
    (block / slot homes), per-slot inputs split on axis 0, and `out` split
    on `out_batch_axis` (0 for (B, S, V) logits; the speculative propose
    scan passes 1 for its (K, B) token stack)."""
    P = jax.sharding.PartitionSpec
    out_spec = P(*([None] * out_batch_axis), "data")
    return jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(None, "data"), P("data"), P("data"), P("data"),
                  P("data")),
        out_specs=(out_spec, P(None, "data")),
        axis_names={"data"}, check_vma=False)


def make_paged_serve_step(cfg, scheme: str, *, paged_kernel: bool = False):
    """The ENGINE's decode step signature (per-slot position vector, active
    mask, block table, pool-shaped caches) — what launch/dryrun lowers for
    decode cells so the cost model prices the paged gather/scatter traffic
    instead of the legacy dense `serve_step`. `paged_kernel` switches the
    attention to the block-table flash-decode kernel (left off for cost
    analysis: the reference path's gather traffic is the thing being
    priced, and Pallas calls are opaque to the HLO cost model)."""
    def paged_serve_step(params, cache, table, tokens, pos, active):
        logits, cache, _ = lm.forward(params, cfg, {"tokens": tokens}, scheme,
                                      jnp.asarray(_SEED), caches=cache,
                                      mode="decode", pos=pos, active=active,
                                      block_table=table,
                                      paged_kernel=paged_kernel)
        return logits, cache
    return paged_serve_step


def greedy_generate(params, cfg, scheme, prompt_tokens, max_new: int,
                    max_len: int | None = None, prompt_lens=None):
    """Simple host-side generation loop (examples / tests / baseline).

    `prompt_tokens` is (B, S) right-padded; `prompt_lens` (B,) gives each
    row's true prompt length (default: all S). Decode runs with a
    per-sequence position vector, so ragged prompts get correct logits for
    attention-cached archs — previously a single scalar `pos` was shared
    across rows, attending pad keys for every short prompt. Recurrent-state
    archs (rwkv / griffin) integrate pad tokens during the single full-width
    prefill, so ragged batches there must go through ServeEngine (which
    prefills per sequence); this loop refuses rather than silently corrupt.

    This is the fixed-batch reference loop: it re-quantizes every weight on
    every step and restarts globally between batches. ServeEngine is the
    production path.
    """
    b, s = prompt_tokens.shape
    max_len = max_len or (s + max_new + 8)
    if cfg.enc_dec:
        raise NotImplementedError("use explicit enc-dec path in examples")
    has_recurrent_state = cfg.family == "ssm" or (
        cfg.family == "hybrid" and any(t == "rec" for t in cfg.griffin.pattern))
    if prompt_lens is not None and has_recurrent_state:
        raise NotImplementedError(
            "ragged prompts on recurrent-state archs: the full-width prefill "
            "would feed pad tokens into wkv/lru state — use serve.engine."
            "ServeEngine, which prefills each sequence at its true length")
    lens = (jnp.full((b,), s, jnp.int32) if prompt_lens is None
            else jnp.asarray(prompt_lens, jnp.int32))
    # Ragged batches need full-capacity sliding-window caches: the ring
    # prefill roll keeps the last `window` positions of the SHARED padded
    # width, which for a short row can evict real keys in favour of pads
    # that then alias earlier absolute positions. Window masking on a flat
    # cache is exact for every row.
    cache = lm.init_cache(cfg, b, max_len, lattn_ring=prompt_lens is None)
    prefill = jax.jit(make_prefill_step(cfg, scheme))
    step = jax.jit(make_serve_step(cfg, scheme))
    logits, cache = prefill(params, cache, {"tokens": prompt_tokens})
    last = logits[jnp.arange(b), lens - 1]          # each row's real last token
    tok = jnp.argmax(last, axis=-1)[:, None]
    out = [tok]
    for i in range(max_new - 1):
        logits, cache = step(params, cache, tok, lens + i)
        tok = jnp.argmax(logits[:, -1:], axis=-1)
        out.append(tok)
    return jnp.concatenate(out, axis=1)
