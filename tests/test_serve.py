"""Serving engine tests: continuous batching, paged KV pool, quantize-once
weights.

Invariants (ISSUE acceptance):
  (a) engine greedy decode == straight-line lm.forward greedy on the same
      tokens (bf16 scheme: exact arithmetic up to masked-softmax padding,
      checked token-for-token);
  (b) paged pool == dense cache BIT-identically (same scatter/gather values,
      same masked attention arithmetic);
  (c) quantize-once packed weights == per-step weight quantization
      BIT-identically (deterministic forward quantizers round-trip through
      the packed form exactly);
  (d) slots and pool blocks are reclaimed when sequences finish.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import lm
from repro.serve.decode import greedy_generate
from repro.serve.engine import (EngineConfig, QueueFull, Request,
                                RequestResult, ServeEngine)
from repro.serve.kv_pool import KVPool
from repro.serve.prequant import prequantize
from repro.serve.sampling import SamplingParams, sample_tokens

pytestmark = pytest.mark.serve

SEED = jnp.array([7, 7], jnp.uint32)


def _cfg(arch):
    cfg = registry.get(arch).reduced()
    if cfg.moe:  # exactness needs no capacity drops (cf. test_archs)
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    return cfg


def _params(cfg):
    return lm.init(cfg, jax.random.PRNGKey(0))


def _prompts(cfg, lens=(9, 13)):
    rng = np.random.RandomState(1)
    return [list(map(int, rng.randint(0, cfg.vocab, n))) for n in lens]


def _engine_tokens(cfg, params, prompts, max_new, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_chunk", 8)
    eng = ServeEngine(cfg, params, EngineConfig(**kw))
    ids = [eng.submit(Request(prompt=p, max_new=max_new)) for p in prompts]
    res = {r.req_id: r.tokens for r in eng.run()}
    return [res[i] for i in ids], eng


def _straightline_tokens(cfg, params, prompt, max_new):
    """Greedy continuation via repeated full forwards (no cache at all)."""
    seq = list(prompt)
    out = []
    for _ in range(max_new):
        logits, _, _ = lm.forward(params, cfg, {"tokens": jnp.asarray([seq])},
                                  "bf16", SEED, mode="train")
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        seq.append(tok)
    return out


# --------------------------------------------------------------------------
# (a) engine decode == straight-line forward
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["yi_9b", "deepseek_v3_671b", "rwkv6_7b",
                                  "recurrentgemma_9b"])
def test_engine_matches_straightline_forward(arch):
    """Chunked prefill + paged continuous-batching decode must reproduce the
    cache-free forward's greedy tokens across mixer families (gqa, mla+moe,
    rwkv, rec+lattn) — ragged prompt lengths in one batch."""
    cfg = _cfg(arch)
    params = _params(cfg)
    prompts = _prompts(cfg)
    got, _ = _engine_tokens(cfg, params, prompts, 5, scheme="bf16",
                            paged=True, prequant=False)
    for p, g in zip(prompts, got):
        assert g == _straightline_tokens(cfg, params, p, 5), arch


def test_engine_quartet2_finite_and_deterministic():
    cfg = _cfg("yi_9b")
    params = _params(cfg)
    prompts = _prompts(cfg)
    a, _ = _engine_tokens(cfg, params, prompts, 6, scheme="quartet2")
    b, _ = _engine_tokens(cfg, params, prompts, 6, scheme="quartet2")
    assert a == b  # deterministic forward quantization + greedy


# --------------------------------------------------------------------------
# (b) paged pool == dense cache, bit-identical
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["yi_9b", "deepseek_v3_671b"])
def test_paged_pool_matches_dense_bitwise(arch):
    cfg = _cfg(arch)
    params = _params(cfg)
    prompts = _prompts(cfg)

    logits_by_mode = {}
    for paged in (False, True):
        eng = ServeEngine(cfg, params,
                          EngineConfig(n_slots=2, max_len=64, prefill_chunk=8,
                                       paged=paged, prequant=False,
                                       scheme="quartet2"))
        for p in prompts:
            eng.submit(Request(prompt=p, max_new=4))
        trace = []
        orig = eng._forward

        def spy(size, tokens, pos, active, _orig=orig, _trace=trace):
            logits = _orig(size, tokens, pos, active)
            _trace.append(np.asarray(logits, np.float32))
            return logits

        eng._forward = spy
        eng.run()
        logits_by_mode[paged] = trace

    dense, paged = logits_by_mode[False], logits_by_mode[True]
    assert len(dense) == len(paged)
    for a, b in zip(dense, paged):
        np.testing.assert_array_equal(a, b)  # BIT-identical logits


# --------------------------------------------------------------------------
# (c) quantize-once == per-step quantization, bit-identical
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["yi_9b", "deepseek_v3_671b", "rwkv6_7b"])
def test_prequant_matches_per_step_bitwise(arch):
    cfg = _cfg(arch)
    params = _params(cfg)
    prompts = _prompts(cfg)

    traces = {}
    for prequant in (False, True):
        eng = ServeEngine(cfg, params,
                          EngineConfig(n_slots=2, max_len=64, prefill_chunk=8,
                                       paged=True, prequant=prequant,
                                       scheme="quartet2"))
        for p in prompts:
            eng.submit(Request(prompt=p, max_new=4))
        trace = []
        orig = eng._forward

        def spy(size, tokens, pos, active, _orig=orig, _trace=trace):
            logits = _orig(size, tokens, pos, active)
            _trace.append(np.asarray(logits, np.float32))
            return logits

        eng._forward = spy
        eng.run()
        traces[prequant] = trace

    assert len(traces[False]) == len(traces[True])
    for a, b in zip(traces[False], traces[True]):
        np.testing.assert_array_equal(a, b)  # BIT-identical logits


def test_prequant_packs_expected_leaves():
    from repro.core.linear import PackedQWeight
    cfg = _cfg("yi_9b")
    params = _params(cfg)
    pq = prequantize(params, cfg, "quartet2")
    mix = pq["stages"][0]["l0"]["mix"]
    assert isinstance(mix["wq"], PackedQWeight)
    assert mix["wq"].packed.dtype == jnp.uint8
    # 4-bit codes: half the bytes of the (N, K) matrix
    assert mix["wq"].packed.shape[-1] == params["stages"][0]["l0"]["mix"]["wq"].shape[-1] // 2
    # embeddings/norms stay raw
    assert not isinstance(pq["embed"], PackedQWeight)
    assert not isinstance(pq["stages"][0]["l0"]["n1"]["g"], PackedQWeight)


def test_prequant_mla_keeps_wkv_b_raw():
    """Absorbed-form decode consumes wkv_b as a raw matrix — must not pack."""
    from repro.core.linear import PackedQWeight
    cfg = _cfg("deepseek_v3_671b")
    params = _params(cfg)
    pq = prequantize(params, cfg, "quartet2")
    mix = pq["stages"][0]["l0"]["mix"]
    assert isinstance(mix["wq_a"], PackedQWeight)
    assert not isinstance(mix["wkv_b"], PackedQWeight)


def test_prequant_bf16_noop():
    cfg = _cfg("yi_9b")
    params = _params(cfg)
    assert prequantize(params, cfg, "bf16") is params


# --------------------------------------------------------------------------
# (d) slot + block reclamation, admission control
# --------------------------------------------------------------------------

def test_slots_and_blocks_reclaimed():
    cfg = _cfg("yi_9b")
    params = _params(cfg)
    eng = ServeEngine(cfg, params,
                      EngineConfig(n_slots=2, max_len=64, prefill_chunk=8,
                                   paged=True, scheme="bf16", prequant=False))
    assert eng.free_slots == 2
    total_blocks = eng.pool.free_block_count
    # 5 requests through 2 slots: continuous batching must cycle slots
    prompts = _prompts(cfg, lens=(9, 13, 7, 11, 5))
    for p in prompts:
        eng.submit(Request(prompt=p, max_new=3))
    results = eng.run()
    assert len(results) == 5
    assert all(len(r.tokens) == 3 for r in results)
    assert eng.free_slots == 2                       # all slots reclaimed
    assert eng.pool.free_block_count == total_blocks  # all blocks reclaimed
    assert eng.stats["finished"] == 5


def test_ring_window_cache_matches_dense_window():
    """Legacy dense decode with cap == window is a true ring buffer: prefill
    roll + ring_abs_pos must reproduce a full-capacity windowed cache EXACTLY
    — including a prompt length NOT divisible by the window (the misaligned
    case: S=13, window=8)."""
    from repro.models import attention as A

    cfg = _cfg("recurrentgemma_9b")
    cfg = dataclasses.replace(
        cfg, griffin=dataclasses.replace(cfg.griffin, window=8))
    key = jax.random.PRNGKey(0)
    p = A.gqa_init(key, cfg)
    s, w = 13, 8
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (1, s, cfg.d_model), jnp.bfloat16) * 0.3

    _, kv = A.gqa_apply(p, x, cfg, "bf16", SEED, 0, causal=True, window=w)
    k, v = kv
    # reference: full-capacity cache, window enforced by masking only
    full = jnp.zeros((1, 32, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    ref_cache = (full.at[:, :s].set(k.astype(jnp.bfloat16)),
                 full.at[:, :s].set(v.astype(jnp.bfloat16)))
    # ring: capacity == window, filled through the prefill roll
    ring = (jnp.zeros((1, w, cfg.n_kv_heads, cfg.hd), jnp.bfloat16),) * 2
    ring_cache = lm._fill_cache(ring, kv, w)

    for i in range(4):  # decode across several wrap points
        step = jax.random.normal(jax.random.fold_in(key, 10 + i),
                                 (1, 1, cfg.d_model), jnp.bfloat16) * 0.3
        o_ref, ref_cache = A.gqa_decode(p, step, cfg, "bf16", SEED, 0,
                                        ref_cache, s + i, window=w)
        o_ring, ring_cache = A.gqa_decode(p, step, cfg, "bf16", SEED, 0,
                                          ring_cache, s + i, window=w)
        np.testing.assert_array_equal(np.asarray(o_ref, np.float32),
                                      np.asarray(o_ring, np.float32))


def test_admission_defers_until_reserved_blocks_free():
    """Admission must account for blocks already COMMITTED to admitted
    sequences (allocation is lazy): with a pool of 6 blocks and two requests
    needing 4 each, the second waits — and both still complete."""
    cfg = _cfg("yi_9b")
    params = _params(cfg)
    eng = ServeEngine(cfg, params,
                      EngineConfig(n_slots=2, max_len=64, block_size=16,
                                   n_blocks=6, prefill_chunk=8,
                                   scheme="bf16", prequant=False))
    for _ in range(2):
        eng.submit(Request(prompt=[1] * 16, max_new=47))  # 63 tok = 4 blocks
    results = eng.run()  # would raise OutOfBlocks without reservations
    assert len(results) == 2
    assert all(len(r.tokens) == 47 for r in results)
    assert eng.pool.free_block_count == 6


def test_unservable_request_rejected_at_submit():
    """A request needing more blocks than the pool has must be rejected at
    submit() — otherwise it head-of-line blocks the FIFO forever."""
    cfg = _cfg("yi_9b")
    params = _params(cfg)
    eng = ServeEngine(cfg, params,
                      EngineConfig(n_slots=1, max_len=64, block_size=16,
                                   n_blocks=2, scheme="bf16", prequant=False))
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=[1] * 40, max_new=10))  # 50 tok = 4 blocks


def test_greedy_generate_ragged_rejects_recurrent_archs():
    """Full-width prefill would feed pads into recurrent state; the loop
    must refuse (ServeEngine is the ragged path for ssm/hybrid)."""
    cfg = _cfg("rwkv6_7b")
    params = _params(cfg)
    with pytest.raises(NotImplementedError):
        greedy_generate(params, cfg, "bf16", jnp.zeros((2, 8), jnp.int32), 2,
                        prompt_lens=jnp.asarray([4, 8]))


def test_admission_control_queue_full():
    cfg = _cfg("yi_9b")
    params = _params(cfg)
    eng = ServeEngine(cfg, params,
                      EngineConfig(n_slots=1, max_len=64, max_queue=2,
                                   scheme="bf16", prequant=False))
    eng.submit(Request(prompt=[1, 2, 3], max_new=2))
    eng.submit(Request(prompt=[1, 2, 3], max_new=2))
    with pytest.raises(QueueFull):
        eng.submit(Request(prompt=[1, 2, 3], max_new=2))
    with pytest.raises(ValueError):  # request longer than max_len
        ok = ServeEngine(cfg, params, EngineConfig(n_slots=1, max_len=16,
                                                   scheme="bf16",
                                                   prequant=False))
        ok.submit(Request(prompt=list(range(15)), max_new=8))


def test_pool_oob_sentinel_drops_writes():
    """Device-side masking convention: writes through unallocated block-table
    entries vanish; gathers of unallocated blocks read zeros."""
    from repro.serve.kv_pool import gather_view, scatter_tokens
    pool = jnp.zeros((4, 4, 2), jnp.bfloat16)          # (P, BS, feat)
    table = jnp.full((2, 2), 4, jnp.int32)             # all OOB sentinel
    table = table.at[0, 0].set(1)                      # row 0 owns block 1
    positions = jnp.array([[0], [0]], jnp.int32)
    vals = jnp.ones((2, 1, 2), jnp.bfloat16)
    valid = jnp.array([[True], [True]])
    pool = scatter_tokens(pool, table, positions, vals, valid)
    view = np.asarray(gather_view(pool, table), np.float32)
    assert view[0, 0].sum() == 2.0                     # row 0 wrote via block 1
    assert view[1].sum() == 0.0                        # row 1 dropped (OOB)
    assert np.asarray(pool, np.float32)[0].sum() == 0  # block 0 untouched


@pytest.mark.parametrize("arch", ["yi_9b", "deepseek_v3_671b",
                                  "recurrentgemma_9b"])
@pytest.mark.parametrize("paged_kernel", [False, True],
                         ids=["gather", "kernel"])
def test_donated_step_keeps_unwritten_pool_bits(arch, paged_kernel):
    """The engine step carries the donated pool through its layer scan and
    updates it in place. Every pool position the step does not write (other
    blocks, an inactive row's blocks, offsets past the chunk), and every
    recurrent state row it does not run, comes back bit for bit; the rest
    equals the same step without donation."""
    from repro.serve import decode as D
    from repro.serve.kv_pool import STATE_KINDS, TOKEN_KINDS
    cfg = _cfg(arch)
    n_slots, max_len, bs, sq = 3, 32, 4, 3
    pool = KVPool(cfg, n_slots, max_len, block_size=bs, n_blocks=12)
    rng = np.random.RandomState(3)
    # garbage everywhere, so an untouched position is told from a zeroed one
    caches = jax.tree.map(
        lambda a: jnp.asarray(rng.randn(*a.shape), a.dtype), pool.caches)
    table = np.full((n_slots, pool.max_blocks), pool.sentinel, np.int32)
    table[0, :3], table[1, :2], table[2, :1] = [7, 2, 9], [4, 11], [0]
    pos = np.array([5, 1, 0], np.int32)
    active = np.array([True, False, True])
    tokens = jnp.asarray(rng.randint(0, cfg.vocab, (n_slots, sq)), jnp.int32)
    args = (jnp.asarray(np.stack([table, table], 1)), tokens,
            jnp.asarray(pos), jnp.asarray(active))
    step = D.make_paged_serve_step(cfg, "bf16", paged_kernel=paged_kernel)
    params = _params(cfg)
    before = jax.tree.map(np.asarray, caches)
    want_logits, want = jax.jit(step)(params, caches, *args)
    logits, got = jax.jit(step, donate_argnums=(1,))(
        params, jax.tree.map(jnp.copy, caches), *args)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    jax.tree.map(lambda g, w: np.testing.assert_array_equal(
        np.asarray(g), np.asarray(w)), got, want)

    written = np.zeros((pool.n_blocks, bs), bool)
    for b in np.flatnonzero(active):
        for p in range(pos[b], pos[b] + sq):
            written[table[b, p // bs], p % bs] = True
    n_checked = 0
    for stage_got, stage_before in zip(got, before):
        for lk, kinds in stage_got.items():
            for kind, leaf in kinds.items():
                for g, b in zip(jax.tree.leaves(leaf),
                                jax.tree.leaves(stage_before[lk][kind])):
                    g = np.asarray(g)
                    if kind in TOKEN_KINDS:     # (L, P, BS, ...)
                        np.testing.assert_array_equal(g[:, ~written],
                                                      b[:, ~written])
                        assert all((g[i][written] != b[i][written]).any()
                                   for i in range(len(g)))
                    else:                       # (L, n_slots, ...)
                        assert kind in STATE_KINDS
                        np.testing.assert_array_equal(g[:, ~active],
                                                      b[:, ~active])
                    n_checked += 1
    assert n_checked >= 2


# --------------------------------------------------------------------------
# satellite: ragged prompts through the legacy greedy loop
# --------------------------------------------------------------------------

def test_greedy_generate_ragged_prompts():
    """greedy_generate(prompt_lens=...) must equal per-row generation —
    the old shared-scalar `pos` produced wrong logits for short rows."""
    cfg = _cfg("yi_9b")
    params = _params(cfg)
    rng = np.random.RandomState(3)
    lens = [6, 10]
    s = max(lens)
    rows = [rng.randint(0, cfg.vocab, n) for n in lens]
    padded = np.zeros((2, s), np.int32)
    for i, r in enumerate(rows):
        padded[i, : len(r)] = r
    out = greedy_generate(params, cfg, "bf16", jnp.asarray(padded), 4,
                          prompt_lens=jnp.asarray(lens))
    for i, r in enumerate(rows):
        solo = greedy_generate(params, cfg, "bf16",
                               jnp.asarray(r[None, :]), 4)
        assert out[i].tolist() == solo[0].tolist(), f"row {i}"


def _lattn_cfg():
    """A pure sliding-window-attention stack: recurrentgemma's hybrid family
    with every pattern slot set to 'attn' (window=8 so the window binds)."""
    base = registry.get("recurrentgemma_9b").reduced()
    return dataclasses.replace(
        base, griffin=dataclasses.replace(base.griffin, window=8,
                                          pattern=("attn", "attn")))


@pytest.mark.parametrize("make_cfg", [lambda: _cfg("yi_9b"),
                                      lambda: _cfg("deepseek_v3_671b"),
                                      _lattn_cfg],
                         ids=["attention", "mla", "lattn"])
def test_ragged_prompts_engine_matches_greedy_generate(make_cfg, base_key,
                                                       np_rng):
    """Cross-arch ragged-prompt regression (locks in the PR-1 position-vector
    fix): mixed-length prompts through the legacy greedy loop and through
    the engine must produce identical tokens for attention / mla / lattn.
    The lattn case also pins the full-capacity (non-ring) ragged cache."""
    cfg = make_cfg()
    params = lm.init(cfg, base_key)
    lens = [6, 10, 13]
    rows = [np_rng.randint(0, cfg.vocab, n) for n in lens]
    padded = np.zeros((3, max(lens)), np.int32)
    for i, r in enumerate(rows):
        padded[i, : len(r)] = r
    legacy = greedy_generate(params, cfg, "bf16", jnp.asarray(padded), 4,
                             prompt_lens=jnp.asarray(lens))
    eng = ServeEngine(cfg, params,
                      EngineConfig(n_slots=3, max_len=64, prefill_chunk=8,
                                   scheme="bf16", prequant=False))
    ids = [eng.submit(Request(prompt=list(map(int, r)), max_new=4))
           for r in rows]
    res = {r.req_id: r.tokens for r in eng.run()}
    for i, rid in enumerate(ids):
        assert res[rid] == legacy[i].tolist(), f"row {i}"


def test_sampler_modes():
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray([[0.0, 5.0, 1.0, 2.0]] * 3)
    temps = jnp.asarray([0.0, 1.0, 1.0], jnp.float32)
    topk = jnp.asarray([0, 1, 0], jnp.int32)
    toks = sample_tokens(logits, temps, topk, key)
    assert int(toks[0]) == 1          # greedy row -> argmax
    assert int(toks[1]) == 1          # top-1 row -> argmax regardless of noise
    assert 0 <= int(toks[2]) < 4
    # temperature sampling covers multiple tokens over draws
    seen = {int(sample_tokens(logits, temps, topk,
                              jax.random.PRNGKey(i))[2]) for i in range(64)}
    assert len(seen) > 1


# --------------------------------------------------------------------------
# radix-tree prefix cache (serve/prefix_cache.py): zero prefill over the
# shared prefix, bitwise stream parity hot vs cold (ISSUE 5 acceptance)
# --------------------------------------------------------------------------

def _cache_engine(cfg, params, prefix_cache, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("scheme", "bf16")
    kw.setdefault("prequant", False)
    return ServeEngine(cfg, params, EngineConfig(prefix_cache=prefix_cache,
                                                 **kw))


def _wave(eng, prompts, max_new=4):
    ids = [eng.submit(Request(prompt=p, max_new=max_new)) for p in prompts]
    res = {r.req_id: r.tokens for r in eng.run()}
    return [res[i] for i in ids]


@pytest.mark.parametrize("arch", ["yi_9b", "deepseek_v3_671b"],
                         ids=["gqa", "mla"])
def test_prefix_cache_skips_prefill_bitwise(arch):
    """A second request sharing an L-token prefix performs ZERO prefill
    forward passes over those L tokens (step-count instrumentation) and its
    greedy stream is BITWISE identical to a cold-cache run — gqa and mla,
    paged pools."""
    cfg = _cfg(arch)
    params = _params(cfg)
    rng = np.random.RandomState(2)
    prompt = list(map(int, rng.randint(0, cfg.vocab, 24)))

    cold_eng = _cache_engine(cfg, params, False)
    cold1 = _wave(cold_eng, [prompt])
    cold2 = _wave(cold_eng, [prompt])          # same engine, cache off

    hot_eng = _cache_engine(cfg, params, True)
    hot1 = _wave(hot_eng, [prompt])
    assert hot1 == cold1                        # empty cache: identical
    steps0 = hot_eng.stats["prefill_steps"]
    tokens0 = hot_eng.stats["prefill_tokens"]
    hot2 = _wave(hot_eng, [prompt])
    assert hot2 == cold2                        # BITWISE parity, hot
    # the full 24-token prompt caps at 23 matched tokens (the last prompt
    # token is always computed for its logits): exactly ONE prefill forward
    # over exactly ONE token — zero forward passes over the L=23 prefix
    assert hot_eng.stats["prefill_steps"] - steps0 == 1
    assert hot_eng.stats["prefill_tokens"] - tokens0 == 1
    assert hot_eng.stats["prefill_skipped_tokens"] == 23
    assert hot_eng.stats["prefix_hits"] == 1


def test_prefix_cache_cow_at_divergence():
    """A prompt diverging INSIDE a cached block reuses the in-block common
    prefix via copy-on-write and only prefills from the divergence on."""
    cfg = _cfg("yi_9b")
    params = _params(cfg)
    rng = np.random.RandomState(3)
    base = list(map(int, rng.randint(0, cfg.vocab, 24)))
    fork = base[:10] + list(map(int, rng.randint(0, cfg.vocab, 14)))

    cold = _wave(_cache_engine(cfg, params, False), [fork])
    hot_eng = _cache_engine(cfg, params, True)
    _wave(hot_eng, [base])                      # prime the cache
    t0 = hot_eng.stats["prefill_tokens"]
    hot = _wave(hot_eng, [fork])
    assert hot == cold                          # bitwise despite COW
    # 10 matched = 2 full aliased blocks (bs=4) + 2 tokens COW'd: prefill
    # covers exactly the 14 unmatched tokens
    assert hot_eng.stats["prefill_tokens"] - t0 == 14
    assert hot_eng.stats["prefill_skipped_tokens"] == 10


def test_prefix_cache_excluded_on_windowed_lattn():
    """Sliding-window stacks reclaim blocks mid-sequence, so their prefixes
    are unshareable: the engine must run with cache=None and emit exactly
    the cache-off streams."""
    cfg = _lattn_cfg()
    params = _params(cfg)
    rng = np.random.RandomState(4)
    prompt = list(map(int, rng.randint(0, cfg.vocab, 12)))
    hot_eng = _cache_engine(cfg, params, True, max_len=32)
    assert hot_eng.cache is None                # excluded, not an error
    cold_eng = _cache_engine(cfg, params, False, max_len=32)
    assert _wave(hot_eng, [prompt]) == _wave(cold_eng, [prompt])
    assert _wave(hot_eng, [prompt]) == _wave(cold_eng, [prompt])
    assert hot_eng.stats["prefill_skipped_tokens"] == 0


def test_prefix_cache_excluded_on_recurrent_state():
    """wkv/lru state integrates the whole prefix into O(1) slot state that
    blocks cannot reconstruct — recurrent archs must be excluded too."""
    cfg = _cfg("rwkv6_7b")
    params = _params(cfg)
    eng = _cache_engine(cfg, params, True)
    assert eng.cache is None


def test_prefix_cache_eviction_under_pressure():
    """When the pool runs dry, unpinned cached prefixes are evicted LRU and
    their blocks reused; every request still completes and the pool fully
    reclaims afterwards."""
    cfg = _cfg("yi_9b")
    params = _params(cfg)
    rng = np.random.RandomState(5)
    prompts = [list(map(int, rng.randint(0, cfg.vocab, 16)))
               for _ in range(4)]
    # 16 blocks of 4 = 64 positions total; each request needs ~5-6 blocks,
    # so caching all four retired streams MUST evict earlier entries
    eng = _cache_engine(cfg, params, True, n_slots=2, max_len=32,
                        n_blocks=16)
    for p in prompts:
        got = _wave(eng, [p])
        assert len(got[0]) == 4
    assert eng.cache.stats["evicted_blocks"] > 0
    # conservation: cached + free == all (no slot is live)
    assert (eng.pool.free_block_count + eng.cache.cached_blocks()
            == eng.pool.n_blocks)
    # hot reuse still correct after the evictions
    cold = _wave(_cache_engine(cfg, params, False, n_slots=2, max_len=32,
                               n_blocks=16), [prompts[-1]])
    assert _wave(eng, [prompts[-1]]) == cold


def test_prefix_cache_quartet2_deterministic():
    """Quantizing schemes are chunk-coupled (shared activation absmax), so
    hot runs are not bit-compared to cold — but they must be deterministic
    run-to-run (docs/CONVENTIONS.md §3)."""
    cfg = _cfg("yi_9b")
    params = _params(cfg)
    rng = np.random.RandomState(6)
    prompt = list(map(int, rng.randint(0, cfg.vocab, 20)))

    def run_twice():
        eng = _cache_engine(cfg, params, True, scheme="quartet2",
                            prequant=True)
        return _wave(eng, [prompt]) + _wave(eng, [prompt])

    assert run_twice() == run_twice()


def test_prefix_cache_spec_decode_composes():
    """Speculative decoding + prefix cache: the draft pool never aliases
    (it catches up over the skipped prefix), and the emitted stream stays
    bitwise equal to the plain engine."""
    cfg = _cfg("yi_9b")
    params = _params(cfg)
    rng = np.random.RandomState(7)
    prompt = list(map(int, rng.randint(0, cfg.vocab, 24)))
    plain = _cache_engine(cfg, params, False)
    ref1, ref2 = _wave(plain, [prompt]), _wave(plain, [prompt])
    eng = _cache_engine(cfg, params, True, spec_k=2, draft_layers=1)
    assert _wave(eng, [prompt]) == ref1
    assert _wave(eng, [prompt]) == ref2
    assert eng.stats["prefill_skipped_tokens"] == 23
    assert eng.stats["spec_rounds"] > 0
