"""Unified language model over every assigned architecture family.

A model is a list of STAGES; each stage is `count` structurally-identical
layers whose parameters are stacked on a leading axis and executed with
jax.lax.scan (keeps HLO size O(1) in depth — essential for 61-layer dry-run
compiles). A layer is (mixer, ff):

    mixer: gqa | lattn (sliding window) | mla | rwkv_tm | rec (RG-LRU)
    ff:    mlp (swiglu/relu2/gelu) | moe | rwkv_cm

Hybrid patterns (recurrentgemma's rec,rec,attn) become stages whose layer spec
is the whole pattern, scanned over pattern repetitions; remainders become a
trailing stage. Whisper (enc_dec) runs an encoder stack then a decoder stack
with cross-attention.

Decode caches are pytrees aligned with the stage structure (stacked on the
same leading axis, consumed/emitted through the same scan). Sliding-window
layers use ring buffers of size `window` — the reason long_500k decode state
stays O(window + d^2) for the hybrid/ssm archs.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import griffin as G
from repro.models import mla as M
from repro.models import moe as X
from repro.models import rwkv6 as W
from repro.models.blocks import (chunked_head_ce, cross_entropy, embed_init,
                                 embed_lookup, lm_head, linear_init,
                                 mlp_apply, mlp_init, norm, norm_init,
                                 site_seed)

# --------------------------------------------------------------------------
# stage structure
# --------------------------------------------------------------------------

def layer_specs(cfg: ArchConfig) -> list[tuple[tuple[tuple[str, str], ...], int]]:
    """[(pattern, repeats)] — pattern is a tuple of (mixer, ff) layer specs."""
    if cfg.family == "ssm":
        return [((("rwkv_tm", "rwkv_cm"),), cfg.n_layers)]
    if cfg.family == "hybrid":
        pat = tuple(("rec", "mlp") if t == "rec" else ("lattn", "mlp")
                    for t in cfg.griffin.pattern)
        reps, rem = divmod(cfg.n_layers, len(pat))
        stages = [(pat, reps)] if reps else []
        if rem:
            stages.append((pat[:rem], 1))
        return stages
    mixer = "mla" if cfg.attn == "mla" else "gqa"
    ff = "moe" if cfg.moe else "mlp"
    return [(((mixer, ff),), cfg.n_layers)]


def total_layers(cfg: ArchConfig) -> int:
    return sum(count * len(pattern) for pattern, count in layer_specs(cfg))


def _prefix_plan(cfg: ArchConfig, n_prefix: int):
    """Cut layer_specs after the first `n_prefix` layers.

    Returns (specs, plan) where plan entries (si, rep_start, reps, pat_len)
    select `reps` repetitions of stage `si` starting at repetition
    `rep_start`, truncated to the first `pat_len` layers of the pattern.
    A cut inside a hybrid pattern yields a trailing partial entry (reps=1),
    so any 0 < n_prefix < total_layers is a valid draft depth.
    """
    specs = layer_specs(cfg)
    total = total_layers(cfg)
    if not 0 < n_prefix < total:
        raise ValueError(
            f"prefix depth must satisfy 0 < n < {total}, got {n_prefix}")
    plan, left = [], n_prefix
    for si, (pattern, count) in enumerate(specs):
        if left <= 0:
            break
        per = len(pattern)
        reps = min(count, left // per)
        if reps:
            plan.append((si, 0, reps, per))
            left -= reps * per
        if left and reps < count:
            plan.append((si, reps, 1, left))
            left = 0
    return specs, plan


def prefix_specs(cfg: ArchConfig, n_prefix: int):
    """layer_specs truncated to the first n_prefix layers (draft stack)."""
    specs, plan = _prefix_plan(cfg, n_prefix)
    return [(specs[si][0][:plen], reps) for si, _, reps, plen in plan]


def prefix_stage_params(params, cfg: ArchConfig, n_prefix: int):
    """Stage-param views positionally aligned with prefix_specs.

    Slices the stacked (count, ...) leaves, so the draft reuses the full
    model's parameters — including PackedQWeight stacks — with the SAME
    per-layer ids (and therefore the same quantization site seeds) as the
    first n_prefix layers of the full forward.
    """
    specs, plan = _prefix_plan(cfg, n_prefix)
    out = []
    for si, r0, reps, plen in plan:
        sp = params["stages"][si]
        sub = {f"l{i}": sp[f"l{i}"] for i in range(plen)}
        if r0 == 0 and reps == specs[si][1] and plen == len(specs[si][0]):
            out.append(sub)
        else:
            out.append(jax.tree.map(lambda x: x[r0:r0 + reps], sub))
    return out


def _mixer_init(key, mixer: str, cfg):
    if mixer in ("gqa", "lattn"):
        return A.gqa_init(key, cfg)
    if mixer == "mla":
        return M.mla_init(key, cfg)
    if mixer == "rwkv_tm":
        return W.rwkv_init(key, cfg)
    if mixer == "rec":
        return G.rglru_init(key, cfg)
    raise ValueError(mixer)


def _ff_init(key, ff: str, cfg):
    if ff == "mlp":
        return mlp_init(key, cfg.d_model, cfg.d_ff, cfg.mlp)
    if ff == "moe":
        return X.moe_init(key, cfg)
    if ff == "rwkv_cm":
        return {}  # rwkv_init already carries channel-mix params
    raise ValueError(ff)


def _layer_init(key, spec, cfg):
    mixer, ff = spec
    km, kf = jax.random.split(key)
    p = {"mix": _mixer_init(km, mixer, cfg),
         "n1": norm_init(cfg.d_model, cfg.norm)}
    if ff != "rwkv_cm":
        p["ff"] = _ff_init(kf, ff, cfg)
    p["n2"] = norm_init(cfg.d_model, cfg.norm)
    return p


def _stack_init(key, pattern, count, cfg):
    """Stacked params: every leaf gets a leading (count,) axis."""
    def one(k):
        ks = jax.random.split(k, len(pattern))
        return {f"l{i}": _layer_init(ks[i], pattern[i], cfg)
                for i in range(len(pattern))}
    return jax.vmap(one)(jax.random.split(key, count))


# --------------------------------------------------------------------------
# layer application
# --------------------------------------------------------------------------

def _merge_state(active, new, old):
    """Keep `new` state rows where active, `old` elsewhere (per batch row).

    Token-cache kinds gate writes inside the mixer (OOB scatter drop); the
    recurrent kinds (wkv / tm_prev / cm_prev / lru) update unconditionally in
    their scans, so a serving batch must restore inactive rows here or a slot
    mid-prefill would be corrupted by the interleaved decode steps."""
    if active is None:
        return new

    def sel(n, o):
        m = active.reshape(active.shape[0], *([1] * (n.ndim - 1)))
        return jnp.where(m, n, o.astype(n.dtype))

    return jax.tree.map(sel, new, old)


def _apply_layer(spec, p, x, cfg, scheme, seed, layer_id, *, mode,
                 cache=None, pos=None, positions=None, enc_out=None,
                 active=None, block_table=None, paged_kernel=False):
    """One (mixer, ff) layer. Returns (x, new_cache_entry, aux)."""
    mixer, ff = spec
    window = cfg.griffin.window if (cfg.griffin and mixer == "lattn") else None
    aux = jnp.zeros((), jnp.float32)
    h = norm(x, p["n1"], cfg.norm, cfg.norm_eps)

    if mixer in ("gqa", "lattn"):
        if mode == "decode":
            o, new_kv = A.gqa_decode(p["mix"], h, cfg, scheme, seed, layer_id,
                                     cache["kv"], pos, window=window,
                                     active=active, block_table=block_table,
                                     paged_kernel=paged_kernel)
            cache = {**cache, "kv": new_kv}
        else:
            o, kv = A.gqa_apply(p["mix"], h, cfg, scheme, seed, layer_id,
                                causal=(mode != "encode"), window=window,
                                positions=positions)
            if cache is not None:
                cache = {**cache, "kv": _fill_cache(cache["kv"], kv, window)}
    elif mixer == "mla":
        if mode == "decode":
            o, new_c = M.mla_decode(p["mix"], h, cfg, scheme, seed, layer_id,
                                    cache["mla"], pos, active=active,
                                    block_table=block_table,
                                    paged_kernel=paged_kernel)
            cache = {**cache, "mla": new_c}
        else:
            o, ckr = M.mla_apply(p["mix"], h, cfg, scheme, seed, layer_id,
                                 positions=positions)
            if cache is not None:
                cache = {**cache, "mla": _fill_cache(cache["mla"], ckr, None)}
    elif mixer == "rwkv_tm":
        st = cache["wkv"] if cache is not None else None
        pv = cache["tm_prev"] if (cache is not None and mode != "train") else None
        o, st, last = W.timemix_apply(p["mix"], h, cfg, scheme, seed, layer_id,
                                      state=st if mode != "train" else None,
                                      prev=pv)
        if cache is not None:
            if mode == "decode":
                st = _merge_state(active, st, cache["wkv"])
                last = _merge_state(active, last, cache["tm_prev"])
            cache = {**cache, "wkv": st, "tm_prev": last}
    elif mixer == "rec":
        st = cache["lru"] if (cache is not None and mode != "train") else None
        o, st = G.recurrent_block_apply(p["mix"], h, cfg, scheme, seed,
                                        layer_id, state=st)
        if cache is not None:
            if mode == "decode":
                st = _merge_state(active, st, cache["lru"])
            cache = {**cache, "lru": st}
    else:
        raise ValueError(mixer)
    x = x + o

    # cross-attention (whisper decoder): between mixer and ff
    if enc_out is not None and "xattn" in p:
        h = norm(x, p["nx"], cfg.norm, cfg.norm_eps)
        o = _cross_attend(p["xattn"], h, enc_out, cfg, scheme, seed, layer_id)
        x = x + o

    h = norm(x, p["n2"], cfg.norm, cfg.norm_eps)
    if ff == "mlp":
        x = x + mlp_apply(p["ff"], h, cfg.mlp, scheme, seed, layer_id)
    elif ff == "moe":
        o, aux = X.moe_apply(p["ff"], h, cfg, scheme, seed, layer_id)
        x = x + o
    elif ff == "rwkv_cm":
        pv = cache["cm_prev"] if (cache is not None and mode != "train") else None
        o, last = W.channelmix_apply(p["mix"], h, cfg, scheme, seed, layer_id,
                                     prev=pv)
        if cache is not None:
            if mode == "decode":
                last = _merge_state(active, last, cache["cm_prev"])
            cache = {**cache, "cm_prev": last}
        x = x + o
    return x, cache, aux


def _fill_cache(buf, new, window):
    """Write prefill K/V (or latents) into a (possibly ring) cache buffer.

    Ring alignment: decode (attention.ring_abs_pos) expects slot j to hold
    the position ≡ j (mod cap), so the last `cap` prefill positions are
    rolled into place rather than written flat — with prompt length S the
    key for position p lands at slot p % cap."""
    def put(b, n):
        n = n.astype(b.dtype)
        s, cap = n.shape[1], b.shape[1]
        if window is not None and s > cap:
            # keep the last `cap` positions S-cap..S-1 and rotate so that
            # position p sits at slot p % cap (roll by S mod cap)
            n = jnp.roll(n[:, -cap:], s % cap, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(b, n, 0, axis=1)
    return jax.tree.map(put, buf, tuple(new) if isinstance(new, tuple) else new)


def _cross_attend(p, h, enc_out, cfg, scheme, seed, layer_id):
    from repro.core.linear import qlinear
    b, s, _ = h.shape
    hd = cfg.hd
    q = qlinear(h, p["wq"], site_seed(seed, layer_id, 30), scheme).reshape(b, s, cfg.n_heads, hd)
    if isinstance(enc_out, tuple):  # precomputed cross K/V (decode)
        k, v = enc_out
    else:
        k = qlinear(enc_out, p["wk"], site_seed(seed, layer_id, 31), scheme)
        v = qlinear(enc_out, p["wv"], site_seed(seed, layer_id, 32), scheme)
        k = k.reshape(b, -1, cfg.n_kv_heads, hd)
        v = v.reshape(b, -1, cfg.n_kv_heads, hd)
    o = A.attend(q, k, v, causal=False)
    return qlinear(o.reshape(b, s, -1), p["wo"], site_seed(seed, layer_id, 33), scheme)


# --------------------------------------------------------------------------
# cache construction
# --------------------------------------------------------------------------

def _layer_cache(spec, cfg, batch: int, max_len: int, *, lattn_ring: bool = True):
    mixer, ff = spec
    hd = cfg.hd
    c: dict[str, Any] = {}
    if mixer in ("gqa", "lattn"):
        cap = max_len
        if mixer == "lattn" and cfg.griffin and lattn_ring:
            cap = min(max_len, cfg.griffin.window)
        kv = jnp.zeros((batch, cap, cfg.n_kv_heads, hd), jnp.bfloat16)
        c["kv"] = (kv, kv)
    elif mixer == "mla":
        m = cfg.mla
        c["mla"] = (jnp.zeros((batch, max_len, m.kv_lora_rank), jnp.bfloat16),
                    jnp.zeros((batch, max_len, m.qk_rope_head_dim), jnp.bfloat16))
    elif mixer == "rwkv_tm":
        h = cfg.d_model // cfg.rwkv.head_dim
        c["wkv"] = jnp.zeros((batch, h, cfg.rwkv.head_dim, cfg.rwkv.head_dim), jnp.float32)
        c["tm_prev"] = jnp.zeros((batch, 1, cfg.d_model), jnp.bfloat16)
    elif mixer == "rec":
        c["lru"] = G.recurrent_state_init(cfg, batch)
    if ff == "rwkv_cm":
        c["cm_prev"] = jnp.zeros((batch, 1, cfg.d_model), jnp.bfloat16)
    return c


def init_cache(cfg: ArchConfig, batch: int, max_len: int, *,
               lattn_ring: bool = True):
    """Stacked cache pytree aligned with layer_specs(cfg).

    lattn_ring=False allocates full max_len capacity for sliding-window
    layers instead of a window-sized ring (required for ragged batches:
    the prefill ring roll assumes one shared prompt length)."""
    stages = []
    for pattern, count in layer_specs(cfg):
        one = {f"l{i}": _layer_cache(pattern[i], cfg, batch, max_len,
                                     lattn_ring=lattn_ring)
               for i in range(len(pattern))}
        stages.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (count, *x.shape)), one))
    return stages


# --------------------------------------------------------------------------
# model init / apply
# --------------------------------------------------------------------------

def init(cfg: ArchConfig, key: jax.Array):
    ks = jax.random.split(key, 8)
    params: dict[str, Any] = {}
    if cfg.input_mode == "tokens" or not cfg.enc_dec:
        params["embed"] = embed_init(ks[0], cfg.vocab, cfg.d_model)
    stages = []
    for i, (pattern, count) in enumerate(layer_specs(cfg)):
        stages.append(_stack_init(jax.random.fold_in(ks[1], i), pattern, count, cfg))
    params["stages"] = stages
    params["final_norm"] = norm_init(cfg.d_model, cfg.norm)
    if not cfg.tie_embeddings:
        params["head"] = linear_init(ks[2], cfg.vocab, cfg.d_model, scale=0.02)
    if cfg.enc_dec:
        params.update(_encdec_extra_init(cfg, ks[3]))
    return params


# Activation checkpointing for the layer scan (train dry-runs at production
# scale assume remat; smoke tests run without). Toggled by launch/dryrun.
REMAT = False


def _run_stages(params, x, cfg, scheme, seed, *, mode, caches=None,
                pos=None, positions=None, enc_out=None, stages=None,
                layer_offset=0, active=None, block_table=None,
                paged_kernel=False):
    specs = stages if stages is not None else layer_specs(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    off = layer_offset
    for si, (pattern, count) in enumerate(specs):
        sp = params["stages"][si]
        cache_s = caches[si] if caches is not None else None

        def body(carry, inp):
            x, aux = carry
            idx, layer_p, layer_c = inp
            new_c = {} if layer_c is not None else None
            for li, spec in enumerate(pattern):
                lid = off + idx * len(pattern) + li
                c_in = layer_c[f"l{li}"] if layer_c is not None else None
                x, c_out, a = _apply_layer(
                    spec, layer_p[f"l{li}"], x, cfg, scheme, seed, lid,
                    mode=mode, cache=c_in, pos=pos, positions=positions,
                    enc_out=enc_out, active=active, block_table=block_table,
                    paged_kernel=paged_kernel)
                if new_c is not None:
                    new_c[f"l{li}"] = c_out
                aux = aux + a
            return (x, aux), new_c

        # remat on every differentiated path (train + the encoder stack that
        # feeds the decoder's training loss); decode/prefill have no backward
        fn = jax.checkpoint(body) if (REMAT and mode in ("train", "encode")) else body
        if cache_s is None:
            (x, aux_total), _ = jax.lax.scan(
                fn, (x, aux_total), (jnp.arange(count), sp, None))
        else:
            (x, aux_total), new_cache_s = jax.lax.scan(
                fn, (x, aux_total), (jnp.arange(count), sp, cache_s))
            new_caches.append(new_cache_s)
        off += count * len(pattern)
    return x, (new_caches if caches is not None else None), aux_total


def head_weight(params, cfg):
    if cfg.enc_dec:
        return params["dec_head"]
    return params["embed"] if cfg.tie_embeddings else params["head"]


def forward(params, cfg: ArchConfig, inputs, scheme: str, seed: jax.Array,
            *, caches=None, mode: str = "train", pos=None, head: bool = True,
            active=None, block_table=None, paged_kernel=False):
    """Full model. inputs: {"tokens": (B,S)} or {"embeds": (B,S,D)} (+ both
    for enc-dec). Returns (logits_or_hidden, new_caches, aux_loss); with
    head=False the final normed hidden states are returned (lm_loss fuses the
    head with a chunked CE so full logits never materialize).

    Decode mode serves ragged batches: `pos` may be a scalar (uniform batch,
    legacy) or a per-sequence (B,) vector; S >= 1 tokens are consumed per row
    (S > 1 = chunked prefill into the cache). `active` (B,) gates cache
    writes per row; `block_table` — (B, MAXB), or (B, 2, MAXB) stacking a
    read table and a write-masked table (prefix-cache aliasing;
    kv_pool.split_tables) — switches kv/mla cache leaves to
    the paged pool layout (see serve/kv_pool.py); `paged_kernel` attends
    through the block-table flash-decode Pallas kernel instead of gathered
    views (kernels/paged_attention.py — requires block_table)."""
    if cfg.enc_dec:
        return _encdec_forward(params, cfg, inputs, scheme, seed,
                               caches=caches, mode=mode, pos=pos, head=head)
    if "embeds" in inputs and mode != "decode":
        x = inputs["embeds"].astype(jnp.bfloat16)
    else:
        x = embed_lookup(params["embed"], inputs["tokens"])
    b, s = x.shape[:2]
    if mode == "decode":
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        positions = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    else:
        positions = jnp.arange(s)[None, :]
    x, caches, aux = _run_stages(params, x, cfg, scheme, seed, mode=mode,
                                 caches=caches, pos=pos, positions=positions,
                                 active=active, block_table=block_table,
                                 paged_kernel=paged_kernel)
    x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    if not head:
        return x, caches, aux
    logits = lm_head(x, head_weight(params, cfg), cfg.quantize_lm_head, scheme, seed)
    return logits, caches, aux


def forward_prefix(params, cfg: ArchConfig, inputs, scheme: str,
                   seed: jax.Array, *, n_prefix: int, caches=None,
                   mode: str = "decode", pos=None, active=None,
                   block_table=None, paged_kernel=False):
    """Early-exit forward: the first `n_prefix` layers + final norm + head.

    This is the self-speculative DRAFT stack (serve/spec_decode.py): it
    reuses the full model's (possibly prequantized) parameters and shared LM
    head — no second model — and runs layers with the same ids/site seeds as
    the full forward, so a draft layer computes bit-for-bit what the same
    layer computes inside the full stack. `caches` must be a prefix-shaped
    pytree (kv_pool.init_cache with specs=prefix_specs(cfg, n_prefix))."""
    if cfg.enc_dec:
        raise NotImplementedError("enc-dec draft stacks are not supported")
    specs = prefix_specs(cfg, n_prefix)
    sub = {"stages": prefix_stage_params(params, cfg, n_prefix)}
    x = embed_lookup(params["embed"], inputs["tokens"])
    b, s = x.shape[:2]
    if mode == "decode":
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        positions = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    else:
        positions = jnp.arange(s)[None, :]
    x, new_caches, aux = _run_stages(sub, x, cfg, scheme, seed, mode=mode,
                                     caches=caches, pos=pos,
                                     positions=positions, stages=specs,
                                     active=active, block_table=block_table,
                                     paged_kernel=paged_kernel)
    x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    logits = lm_head(x, head_weight(params, cfg), cfg.quantize_lm_head,
                     scheme, seed)
    return logits, new_caches, aux


# --------------------------------------------------------------------------
# whisper-style encoder-decoder
# --------------------------------------------------------------------------

def _encdec_extra_init(cfg, key):
    """Decoder stack + cross-attention params; `stages` holds the encoder."""
    ks = jax.random.split(key, 4)
    dec_pattern = (("gqa", "mlp"),)

    def one(k):
        p = _layer_init(k, dec_pattern[0], cfg)
        kx = jax.random.fold_in(k, 7)
        p["xattn"] = A.gqa_init(kx, cfg)
        p["nx"] = norm_init(cfg.d_model, cfg.norm)
        return {"l0": p}

    return {
        "dec_embed": embed_init(ks[0], cfg.vocab, cfg.d_model),
        "dec_stages": [jax.vmap(one)(jax.random.split(ks[1], cfg.n_layers))],
        "dec_final_norm": norm_init(cfg.d_model, cfg.norm),
        "dec_head": linear_init(ks[2], cfg.vocab, cfg.d_model, scale=0.02),
    }


DEC_STAGES = lambda cfg: [((("gqa", "mlp"),), cfg.n_layers)]


def _encdec_forward(params, cfg, inputs, scheme, seed, *, caches, mode, pos,
                    head: bool = True):
    if mode == "decode":
        enc_out = caches["enc_out"]
        x = embed_lookup(params["dec_embed"], inputs["tokens"])
        posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (x.shape[0],))
        positions = posb[:, None] + jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
        dec_params = {"stages": params["dec_stages"]}
        x, new_dec, _ = _run_stages(dec_params, x, cfg, scheme, seed,
                                    mode="decode", caches=caches["dec"],
                                    pos=posb, positions=positions,
                                    enc_out=enc_out, stages=DEC_STAGES(cfg))
        x = norm(x, params["dec_final_norm"], cfg.norm, cfg.norm_eps)
        logits = lm_head(x, params["dec_head"], cfg.quantize_lm_head, scheme, seed)
        return logits, {"enc_out": enc_out, "dec": new_dec}, jnp.zeros((), jnp.float32)

    # encoder (bidirectional over stub audio embeddings)
    xe = inputs["embeds"].astype(jnp.bfloat16)
    se = xe.shape[1]
    enc_x, _, _ = _run_stages(params, xe, cfg, scheme, seed, mode="encode",
                              positions=jnp.arange(se)[None, :])
    enc_out = norm(enc_x, params["final_norm"], cfg.norm, cfg.norm_eps)

    # decoder (causal self-attn + cross-attn)
    x = embed_lookup(params["dec_embed"], inputs["tokens"])
    sd = x.shape[1]
    dec_params = {"stages": params["dec_stages"]}
    x, new_dec, _ = _run_stages(dec_params, x, cfg, scheme, seed, mode=mode,
                                caches=caches["dec"] if caches else None,
                                positions=jnp.arange(sd)[None, :],
                                enc_out=enc_out, stages=DEC_STAGES(cfg))
    x = norm(x, params["dec_final_norm"], cfg.norm, cfg.norm_eps)
    new_caches = ({"enc_out": enc_out, "dec": new_dec} if caches else None)
    if not head:
        return x, new_caches, jnp.zeros((), jnp.float32)
    logits = lm_head(x, params["dec_head"], cfg.quantize_lm_head, scheme, seed)
    return logits, new_caches, jnp.zeros((), jnp.float32)


def init_encdec_cache(cfg: ArchConfig, batch: int, max_len: int, enc_len: int):
    dec = []
    one = {"l0": _layer_cache(("gqa", "mlp"), cfg, batch, max_len)}
    dec.append(jax.tree.map(lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)), one))
    return {"enc_out": jnp.zeros((batch, enc_len, cfg.d_model), jnp.bfloat16),
            "dec": dec}


# --------------------------------------------------------------------------
# losses
# --------------------------------------------------------------------------

def lm_loss(params, cfg, batch, scheme, seed, aux_weight: float = 0.01):
    """Fused chunked head+CE (never materializes (tokens, vocab) logits)."""
    hidden, _, aux = forward(params, cfg, batch, scheme, seed, mode="train",
                             head=False)
    ce = chunked_head_ce(hidden, head_weight(params, cfg), batch["labels"],
                         cfg.quantize_lm_head, scheme, seed)
    return ce + aux_weight * aux
