"""Paged-attention flash-decode kernel parity suite (interpret mode).

Three layers of agreement, per the kernel contract:

  1. kernel vs `kernels.ref` oracle — the oracle IS the serving reference
     path (gather_view + decode_sdpa / the absorbed-form MLA einsums), so
     numeric agreement means the kernel can replace it;
  2. kernel vs an INLINE gather_view + decode_sdpa composition — guards the
     oracle itself against drift;
  3. engine level: `paged_kernel=True` (Pallas, interpret on CPU) must emit
     a greedy token stream BITWISE-identical to the reference path for
     gqa / mla / sliding-window configs, including the speculative
     (n_slots, spec_k+1) verify chunks.

Cases sweep ragged per-row lengths, partially-allocated block tables
(trailing OOB-sentinel entries), fully-unallocated rows (inactive slots),
reclaimed sentinel PREFIXES (sliding-window mid-sequence frees), windowed
masks, and multi-token chunks. Numeric tolerance is fp32 online-softmax
association noise (~1e-7); token streams are compared exactly.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import formats as F
from repro.kernels import ops, ref
from repro.models import lm
from repro.models.attention import decode_sdpa
from repro.serve.engine import EngineConfig, Request, ServeEngine
from repro.serve.kv_pool import gather_view, pack_pair

ATOL, RTOL = 5e-6, 1e-5


# --------------------------------------------------------------------------
# pool/table builders
# --------------------------------------------------------------------------

BS, MAXB, N_BLOCKS = 4, 4, 10


def _mk_table(rng, lens, n_slots, sentinel_prefix=0):
    """Block table backing `lens[i]` tokens per row with RANDOM physical
    blocks (logical order != physical order), trailing entries OOB sentinel.
    `sentinel_prefix` marks leading logical blocks reclaimed (sliding-window
    frees): their entries revert to the sentinel."""
    table = np.full((n_slots, MAXB), N_BLOCKS, np.int32)
    free = list(rng.permutation(N_BLOCKS))
    for i, n in enumerate(lens):
        for j in range(-(-n // BS)):
            table[i, j] = free.pop()
    table[:, :sentinel_prefix] = N_BLOCKS
    return jnp.asarray(table)


def _fill_pool(rng, table, lens, *feat):
    """bf16 pool with real values at every backed (block, offset) position
    and garbage (not zeros!) elsewhere — masked lanes must not leak."""
    pool = rng.randn(N_BLOCKS, BS, *feat) * 7.0  # stale garbage everywhere
    table = np.asarray(table)
    for i, n in enumerate(lens):
        for t in range(n):
            blk = table[i, t // BS]
            if blk < N_BLOCKS:
                pool[blk, t % BS] = rng.randn(*feat) * 0.5
    return jnp.asarray(pool, jnp.bfloat16)


# --------------------------------------------------------------------------
# kernel vs oracle vs inline composition
# --------------------------------------------------------------------------

class TestGQAKernel:
    @pytest.mark.parametrize("sq,window", [(1, None), (1, 6), (3, None),
                                           (3, 6), (4, 11)])
    def test_matches_oracle_and_composition(self, sq, window, np_rng):
        kv, rep, hd, vd = 2, 2, 8, 8
        h = kv * rep
        lens = [5, 11, 16, 0]     # ragged; partial tables; row 3 inactive
        pos = jnp.asarray([max(n - sq, 0) for n in lens], jnp.int32)
        table = _mk_table(np_rng, lens, len(lens))
        kp = _fill_pool(np_rng, table, lens, kv, hd)
        vp = _fill_pool(np_rng, table, lens, kv, vd)
        q = jnp.asarray(np_rng.randn(len(lens), sq, h, hd) * 0.5, jnp.float32)

        out = ops.paged_attention(q, kp, vp, table, pos, window=window)
        want = ref.paged_attention_ref(q, kp, vp, table, pos, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=ATOL, rtol=RTOL)
        # inline composition — today's serving reference path, literally
        inline = decode_sdpa(q, gather_view(kp, table), gather_view(vp, table),
                             pos, window=window)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(inline))
        # the fully-unallocated row is exact zeros on both paths
        assert float(jnp.abs(out[3]).max()) == 0.0
        assert float(jnp.abs(want[3]).max()) == 0.0

    def test_window_reclaimed_sentinel_prefix(self, np_rng):
        """Sliding-window reclamation frees LEADING logical blocks (their
        table entries revert to the sentinel). Those keys sit outside every
        query's window, so kernel and reference agree with the prefix gone."""
        kv, rep, hd = 2, 1, 8
        window, n = 6, 15
        pos = jnp.asarray([n - 1], jnp.int32)
        state = np_rng.get_state()
        full = _mk_table(np_rng, [n], 1)
        kp = _fill_pool(np_rng, full, [n], kv, hd)
        vp = _fill_pool(np_rng, full, [n], kv, hd)
        q = jnp.asarray(np_rng.randn(1, 1, kv * rep, hd) * 0.5, jnp.float32)
        # reclaim horizon: blocks with newest key <= (n-1) - window
        first_live = (n - window) // BS
        np_rng.set_state(state)  # same physical layout, prefix reclaimed
        reclaimed = _mk_table(np_rng, [n], 1, sentinel_prefix=first_live)
        out = ops.paged_attention(q, kp, vp, reclaimed, pos, window=window)
        want_full = ref.paged_attention_ref(q, kp, vp, full, pos, window=window)
        want_recl = ref.paged_attention_ref(q, kp, vp, reclaimed, pos,
                                            window=window)
        np.testing.assert_array_equal(np.asarray(want_full),
                                      np.asarray(want_recl))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want_recl),
                                   atol=ATOL, rtol=RTOL)

    def test_grouped_heads_vs_mha(self, np_rng):
        """rep > 1 must equal running each duplicated KV head as MHA."""
        kv, rep, hd = 2, 3, 8
        lens = [9, 13]
        pos = jnp.asarray([n - 1 for n in lens], jnp.int32)
        table = _mk_table(np_rng, lens, 2)
        kp = _fill_pool(np_rng, table, lens, kv, hd)
        vp = _fill_pool(np_rng, table, lens, kv, hd)
        q = jnp.asarray(np_rng.randn(2, 1, kv * rep, hd) * 0.5, jnp.float32)
        out = ops.paged_attention(q, kp, vp, table, pos)
        kp_m = jnp.repeat(kp, rep, axis=2)
        vp_m = jnp.repeat(vp, rep, axis=2)
        want = ops.paged_attention(q, kp_m, vp_m, table, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=ATOL, rtol=RTOL)


class TestMLAKernel:
    @pytest.mark.parametrize("sq", [1, 3])
    def test_matches_oracle(self, sq, np_rng):
        h, lora, rope, qk_dim = 3, 8, 4, 48
        lens = [6, 14, 0]
        pos = jnp.asarray([max(n - sq, 0) for n in lens], jnp.int32)
        table = _mk_table(np_rng, lens, len(lens))
        cc = _fill_pool(np_rng, table, lens, lora)
        kc = _fill_pool(np_rng, table, lens, rope)
        qa = jnp.asarray(np_rng.randn(len(lens), sq, h, lora) * 0.5,
                         jnp.float32)
        qr = jnp.asarray(np_rng.randn(len(lens), sq, h, rope) * 0.5,
                         jnp.float32)
        out = ops.paged_mla_attention(qa, qr, cc, kc, table, pos,
                                      qk_dim=qk_dim)
        want = ref.paged_mla_attention_ref(qa, qr, cc, kc, table, pos,
                                           qk_dim=qk_dim)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=ATOL, rtol=RTOL)
        assert float(jnp.abs(out[2]).max()) == 0.0  # inactive row


class TestLayerIndexedKernels:
    """The serving step hands every paged kernel the STACKED (L, P, ...)
    pool and a layer index, never a per-layer slice. Reading layer l of the
    stack in place must equal, bit for bit, the call on the sliced pool[l]
    (each layer of the stack holds different values and garbage, so a
    wrong layer cannot pass)."""

    L = 3

    def _stack(self, np_rng, table, lens, *feat, packed=False):
        pools = jnp.stack([_fill_pool(np_rng, table, lens, *feat)
                           for _ in range(self.L)])
        if packed:
            return list(F.nvfp4_cache_encode(pools))
        return [pools]

    @pytest.mark.parametrize("layer", [0, 2])
    @pytest.mark.parametrize("sq,window", [(1, None), (3, None), (3, 6)])
    @pytest.mark.parametrize("packed", [False, True], ids=["bf16", "nvfp4"])
    def test_gqa_layer_equals_slice(self, packed, sq, window, layer, np_rng):
        kv, rep, hd = 2, 2, 32
        lens = [5, 11, 16, 0]
        pos = jnp.asarray([max(n - sq, 0) for n in lens], jnp.int32)
        table = _mk_table(np_rng, lens, len(lens))
        k, v = (self._stack(np_rng, table, lens, kv, hd)[0]
                for _ in range(2))
        # the packed pool holds K and V together (kv_pool.PackedKVPair)
        pool = list(pack_pair(k, v)) if packed else [k, v]
        q = jnp.asarray(np_rng.randn(len(lens), sq, kv * rep, hd) * 0.5,
                        jnp.float32)
        kernel = ops.paged_attention_q if packed else ops.paged_attention
        out = kernel(q, *pool, table, pos, jnp.int32(layer), window=window)
        want = kernel(q, *[a[layer] for a in pool], table, pos,
                      window=window)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
        assert float(jnp.abs(out[:3]).max()) > 0.0

    @pytest.mark.parametrize("layer", [0, 2])
    @pytest.mark.parametrize("sq", [1, 3])
    @pytest.mark.parametrize("packed", [False, True], ids=["bf16", "nvfp4"])
    def test_mla_layer_equals_slice(self, packed, sq, layer, np_rng):
        h, lora, rope, qk_dim = 3, 32, 16, 48
        lens = [6, 14, 0]
        pos = jnp.asarray([max(n - sq, 0) for n in lens], jnp.int32)
        table = _mk_table(np_rng, lens, len(lens))
        cc = self._stack(np_rng, table, lens, lora, packed=packed)
        kc = self._stack(np_rng, table, lens, rope, packed=packed)
        qa = jnp.asarray(np_rng.randn(len(lens), sq, h, lora) * 0.5,
                         jnp.float32)
        qr = jnp.asarray(np_rng.randn(len(lens), sq, h, rope) * 0.5,
                         jnp.float32)
        kernel = (ops.paged_mla_attention_q if packed
                  else ops.paged_mla_attention)
        out = kernel(qa, qr, *cc, *kc, table, pos, jnp.int32(layer),
                     qk_dim=qk_dim)
        want = kernel(qa, qr, *[a[layer] for a in (*cc, *kc)], table, pos,
                      qk_dim=qk_dim)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
        assert float(jnp.abs(out[:2]).max()) > 0.0


# --------------------------------------------------------------------------
# engine level: kernel path == reference path, bitwise token streams
# --------------------------------------------------------------------------

def _cfg(arch):
    cfg = registry.get(arch).reduced()
    if cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    return cfg


def _lattn_cfg():
    base = registry.get("recurrentgemma_9b").reduced()
    return dataclasses.replace(
        base, griffin=dataclasses.replace(base.griffin, window=8,
                                          pattern=("attn", "attn")))


def _streams(cfg, params, prompts, max_new, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prequant", False)
    eng = ServeEngine(cfg, params, EngineConfig(**kw))
    ids = [eng.submit(Request(prompt=p, max_new=max_new)) for p in prompts]
    res = {r.req_id: r.tokens for r in eng.run()}
    return [res[i] for i in ids]


@pytest.mark.serve
class TestEngineKernelPath:
    @pytest.mark.parametrize("make_cfg", [lambda: _cfg("yi_9b"),
                                          lambda: _cfg("deepseek_v3_671b"),
                                          _lattn_cfg],
                             ids=["gqa", "mla", "lattn"])
    def test_greedy_stream_bitwise(self, make_cfg, base_key, np_rng):
        """paged_kernel=True (interpret) emits the SAME tokens as the
        gather_view reference engine — gqa, mla, and lattn (the windowed
        engine also exercises mid-sequence block reclamation: block_size=4
        frees out-of-window blocks while decoding)."""
        cfg = make_cfg()
        params = lm.init(cfg, base_key)
        prompts = [list(map(int, np_rng.randint(0, cfg.vocab, n)))
                   for n in (9, 13)]
        kw = dict(scheme="bf16", paged=True)
        if cfg.griffin is not None:
            kw["block_size"] = 4  # reclamation kicks in mid-stream
        a = _streams(cfg, params, prompts, 6, paged_kernel=False, **kw)
        b = _streams(cfg, params, prompts, 6, paged_kernel=True, **kw)
        assert a == b

    def test_quartet2_stream_bitwise_and_deterministic(self, base_key,
                                                       np_rng):
        """The NVFP4 serving scheme stays greedy-stable under the kernel:
        same stream as the reference path, and deterministic run-to-run."""
        cfg = _cfg("yi_9b")
        params = lm.init(cfg, base_key)
        prompts = [list(map(int, np_rng.randint(0, cfg.vocab, n)))
                   for n in (9, 13)]
        a = _streams(cfg, params, prompts, 5, scheme="quartet2",
                     paged_kernel=False)
        b = _streams(cfg, params, prompts, 5, scheme="quartet2",
                     paged_kernel=True)
        c = _streams(cfg, params, prompts, 5, scheme="quartet2",
                     paged_kernel=True)
        assert a == b == c

    def test_spec_decode_verify_chunk_through_kernel(self, base_key, np_rng):
        """The (n_slots, spec_k+1) verify chunk runs through the kernel's
        multi-token path; the emitted stream must still equal the
        non-speculative kernel engine bitwise (bf16 chunk invariance)."""
        cfg = _cfg("yi_9b")
        params = lm.init(cfg, base_key)
        prompts = [list(map(int, np_rng.randint(0, cfg.vocab, n)))
                   for n in (9, 13)]
        plain = _streams(cfg, params, prompts, 6, scheme="bf16",
                         paged_kernel=True)
        spec = _streams(cfg, params, prompts, 6, scheme="bf16",
                        paged_kernel=True, spec_k=2, draft_layers=1)
        assert plain == spec

    def test_paged_kernel_requires_paged(self, base_key):
        cfg = _cfg("yi_9b")
        params = lm.init(cfg, base_key)
        with pytest.raises(ValueError):
            ServeEngine(cfg, params,
                        EngineConfig(n_slots=1, max_len=32, paged=False,
                                     paged_kernel=True, prequant=False,
                                     scheme="bf16"))

    def test_default_resolves_reference_path_on_cpu(self):
        """The knob's default is backend-resolved: reference path on CPU
        (kernel would only run interpreted), kernel path on TPU."""
        e = EngineConfig()
        assert e.resolved_paged_kernel() == (jax.default_backend() == "tpu")
        assert EngineConfig(paged_kernel=True).resolved_paged_kernel()
