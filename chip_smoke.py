"""Bring-up smoke run of the main paths on TPU, at llama-200m's published
width with random weights made from `--seed`.

    python3 chip_smoke.py              # one chip: train, then serve
    python3 chip_smoke.py --chips 4    # four chips: mesh-sharded serving only

One process holds the chip(s) for the whole run, and every phase raises on
failure, so any failed check exits non-zero. Without a TPU the script exits
non-zero before any phase. The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.

Phases (one chip):
  train   `make_train_step` + `Trainer` (the `launch/train.py` path), scheme
          quartet2, a few steps; every loss must be finite.
  kernel  the compiled Pallas paged-attention kernels (bf16 and NVFP4-packed
          pools) against float64 attention on random pools.
  serve   `ServeEngine` defaults (paged pool, prequantized weights, the
          Pallas kernel resolved on TPU), for the bf16 and the NVFP4-packed
          pool (`kv_quant`): the kernel must be compiled into the decode
          step. Every decode step of the kernel engine is also run through
          the gather reference (`paged_kernel=False`) on the same pool
          state: under bf16 for both pools (bounded), and under quartet2
          for the bf16 pool (reported).
          The default engine also serves the requests over HTTP through
          `EngineBridge` + `CompletionFrontend` on localhost.
Phase (--chips 4):
  sharded the engine on a (data=2, model=2) mesh against a one-chip engine
          on the same requests, with each device's params and pool bytes.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "llama_200m"
# Train size, from the step's memory_analysis compiled for one v5e: seq 512
# x batch 4 needs 8.5 GB (3.4 GB state in, 3.4 GB out, 1.8 GB temporaries)
# of the chip's 16 GB; seq 1024 x batch 8 needs 19.7 GB and does not fit.
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 512, 4, 4
N_REQUESTS, PROMPT_LENS, NEW_TOKENS = 8, (64, 512), 32
N_SLOTS = 8
MAX_LEN = -(-(PROMPT_LENS[1] + NEW_TOKENS) // 16) * 16

# Compiled kernel vs float64 attention over random pools at the model's
# widths. q, K and V are bf16 (exact in float64); the kernel sums in f32,
# may round probabilities to bf16 inside its matmuls (2^-9 relative) and
# casts its output to bf16 (2^-9 relative). A wrong block, row or head costs
# O(max |out|): max |kernel - float64| <= 2^-6 of max |float64 out|.
KERNEL_ATTN_RTOL = 2.0 ** -6
# Kernel step vs gather-reference step on the same pool state, one chip,
# under the bf16 scheme. The two attention paths differ at the bf16-rounding
# level (f32 sums in another order, probabilities rounded to bf16 in
# different places), and the network carries that to the logits roughly
# linearly: injecting 2^-9 relative noise into every attention output of
# llama-200m moves its logits by 1.6% of the largest (train-mode forward on
# the CPU). A wrong kernel moves them by O(max |logit|): max |kernel -
# reference| <= 5% of max |reference logit|. Under the default quartet2
# scheme the same noise moves logits by 35% and flips 60% of greedy tokens,
# because every activation is re-quantized to NVFP4 with round-to-nearest;
# that comparison is reported, not bounded. Kernel and reference engines
# are not run side by side: their pools differ after prefill, and an NVFP4
# pool re-quantizes every cached K/V, which amplifies that difference the
# same way (free-running bf16-scheme engines on an NVFP4 pool, one v5e:
# first-decode logits up to 2.79 apart, 9% of stream tokens equal).
KERNEL_LOGIT_RTOL = 0.05
# Sharded (data=2, model=2) vs one chip, both under the bf16 scheme:
# quartet2's per-tensor activation scale spans the local slot batch, so a
# data split changes the quantization grid (serve/README.md); under bf16
# the only difference is where the "model"-split GEMMs are summed, i.e.
# bf16 rounding of partial sums (2^-8 relative per rounding) through the
# network: max |sharded - single| <= 5% of max |single logit|.
SHARDED_LOGIT_RTOL = 0.05
# Serving runs: (kv_quant, scheme, bound on the kernel-vs-reference logits
# as a share of max |reference logit|; inf: reported, not bounded; None: no
# reference steps). The gather reference over an NVFP4 pool is slow on a
# v5e (about three minutes of reference steps per run), so that pool meets
# it under bf16 only, the scheme whose comparison is bounded.
SERVE_RUNS = ((False, "quartet2", np.inf), (False, "bf16", KERNEL_LOGIT_RTOL),
              (True, "quartet2", None), (True, "bf16", KERNEL_LOGIT_RTOL))


T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def device_gate(chips: int):
    """Refuse to run anywhere but on `chips` TPU chips: no CPU fallback."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
                 "nothing was run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU chips, "
                 f"JAX sees {len(devices)}")
    log(f"jax {jax.__version__}; device_kind {devices[0].device_kind}; "
        f"{len(devices)} device(s)")
    return devices


def train_phase(cfg, *, seq: int, batch: int, steps: int, seed: int) -> dict:
    from repro.data.pipeline import DataConfig, SyntheticCorpus
    from repro.models import lm
    from repro.train.train_step import make_train_step
    from repro.train.trainer import Trainer, TrainerConfig

    corpus = SyntheticCorpus(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                        global_batch=batch, seed=seed))
    init_state, train_step = make_train_step(cfg, "quartet2", base_lr=2e-3,
                                             total_steps=steps)
    state = init_state(lm.init(cfg, jax.random.PRNGKey(seed)))
    t0 = time.perf_counter()
    step = jax.jit(train_step).lower(state, corpus.batch_at(0)).compile()
    compile_s = time.perf_counter() - t0
    with tempfile.TemporaryDirectory() as ckpt:
        trainer = Trainer(TrainerConfig(total_steps=steps, ckpt_dir=ckpt,
                                        log_every=1), step, corpus)
        trainer.run(state, resume=False)
    losses = [h["loss"] for h in trainer.history]
    step_s = [h["dt"] for h in trainer.history[1:]]
    log(f"[train] {cfg.name} quartet2 seq {seq} x batch {batch}: compile "
        f"{compile_s:.2f}s; losses {losses}; step seconds after the first "
        f"{step_s}")
    if len(losses) != steps or not np.all(np.isfinite(losses)):
        raise RuntimeError(f"train: expected {steps} finite losses, got "
                           f"{losses}")
    return {"compile_s": compile_s, "losses": losses, "step_s": step_s}


def attention_f64(q, k_pool, v_pool, table, pos):
    """Float64 causal attention over the gathered pool view: the math of
    `kernels.ref.paged_attention_ref` on the host."""
    q, k_pool, v_pool = (np.asarray(a, np.float64) for a in (q, k_pool, v_pool))
    b, sq, h, hd = q.shape
    kv = k_pool.shape[2]
    idx = np.minimum(table, len(k_pool) - 1)    # sentinel blocks are causal-masked
    k = k_pool[idx].reshape(b, -1, kv, hd)
    v = v_pool[idx].reshape(b, -1, kv, v_pool.shape[-1])
    qg = q.reshape(b, sq, kv, h // kv, hd)
    s = np.einsum("bqgrd,btgd->bgrqt", qg, k) / np.sqrt(hd)
    qpos = pos[:, None] + np.arange(sq)[None, :]
    ok = np.arange(k.shape[1])[None, None, :] <= qpos[:, :, None]
    s = np.where(ok[:, None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bgrqt,btgv->bqgrv", p, v).reshape(b, sq, h, -1)


def kernel_phase(cfg, *, seed: int) -> dict:
    """The compiled paged-attention kernels (bf16 and NVFP4-packed pools)
    against float64 attention, at the model's head widths and the serving
    pool geometry, for decode (Sq=1) and prefill-chunk (Sq=16) queries."""
    from repro.kernels import ops
    from repro.serve.kv_pool import pack_pair, unpack_pair

    rng = np.random.RandomState(seed)
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    maxb = MAX_LEN // 16
    n_blocks = N_SLOTS * maxb
    out = {}
    for sq in (1, 16):
        lens = rng.randint(sq, MAX_LEN + 1, N_SLOTS)
        table = np.full((N_SLOTS, maxb), n_blocks, np.int32)
        free = iter(rng.permutation(n_blocks))
        for i, n in enumerate(lens):
            nb = -(-n // 16)
            table[i, :nb] = [next(free) for _ in range(nb)]
        pos = (lens - sq).astype(np.int32)
        q = jnp.asarray(rng.randn(N_SLOTS, sq, h, hd), jnp.bfloat16)
        k, v = (jnp.asarray(rng.randn(n_blocks, 16, kv, hd), jnp.bfloat16)
                for _ in range(2))
        kvq = pack_pair(k, v)       # the NVFP4 pool holds K and V together
        for pool, got, want in (
                ("bf16", ops.paged_attention(q, k, v, table, pos),
                 attention_f64(q, k, v, table, pos)),
                ("nvfp4", ops.paged_attention_q(q, *kvq, table, pos),
                 attention_f64(q, *unpack_pair(kvq), table, pos))):
            err = float(np.max(np.abs(np.asarray(got, np.float64) - want)))
            bound = KERNEL_ATTN_RTOL * float(np.max(np.abs(want)))
            log(f"[kernel] {pool} pool, Sq={sq}: max |kernel - float64| "
                f"{err} (bound {bound})")
            if not err <= bound:
                raise RuntimeError(f"kernel {pool} Sq={sq}: error {err} > "
                                   f"{bound}")
            out[f"{pool}_sq{sq}"] = err
    return out


def make_prompts(vocab: int, seed: int) -> list[list[int]]:
    rng = np.random.RandomState(seed)
    lens = rng.randint(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [list(map(int, rng.randint(0, vocab, n))) for n in lens]


def run_direct(eng, prompts, ref_step=None) -> dict:
    """Serve `prompts` in submission order. Returns the greedy `streams`
    and, stacked in the same order, each request's logits at its first
    decode (one-token) step (`first`). With `ref_step` (an engine-step
    function), every decode step is first run through it on the same
    params, pool and inputs (before the engine's step donates the pool);
    then the reference's first-decode logits (`first_ref`) and the share of
    decode steps whose greedy token both agree on (`step_agreement`) are
    returned too."""
    from repro.serve.engine import DECODE, Request

    first, first_ref, same = {}, {}, []
    dispatch = eng._forward

    def keep_first_decode(size, tokens, pos, active):
        # one-token prompt tails are prefilled with size 1 too
        rows = [i for i in np.flatnonzero(active)
                if eng.slots[i].state == DECODE]
        if rows and ref_step is not None:
            want, _ = ref_step(eng.params, eng.pool.caches,
                               eng.pool.tables_device(), jnp.asarray(tokens),
                               jnp.asarray(pos), jnp.asarray(active))
            want = np.asarray(want[:, -1], np.float32)
        logits = dispatch(size, tokens, pos, active)
        if rows:
            got = np.asarray(logits[:, -1], np.float32)
            for i in rows:
                rid = eng.slots[i].req.req_id
                first.setdefault(rid, got[i])
                if ref_step is not None:
                    first_ref.setdefault(rid, want[i])
                    same.append(got[i].argmax() == want[i].argmax())
        return logits

    eng._forward = keep_first_decode
    ids = [eng.submit(Request(prompt=p, max_new=NEW_TOKENS)) for p in prompts]
    res = {r.req_id: r.tokens for r in eng.run()}
    eng._forward = dispatch
    out = {"streams": [res[i] for i in ids],
           "first": np.stack([first[i] for i in ids])}
    if ref_step is not None:
        out["first_ref"] = np.stack([first_ref[i] for i in ids])
        out["step_agreement"] = float(np.mean(same))
    return out


def decode_step_hlo(eng) -> str:
    """Optimized HLO of the engine's compiled one-token step."""
    n = eng.econf.n_slots
    return eng._step_fns[1].lower(
        eng.params, eng.pool.caches, eng.pool.tables_device(),
        np.zeros((n, 1), np.int32), np.zeros((n,), np.int32),
        np.zeros((n,), bool)).compile().as_text()


async def _complete(port: int, prompt: list[int], max_new: int) -> list[int]:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps({"prompt": prompt, "max_tokens": max_new}).encode()
    writer.write((f"POST /v1/completions HTTP/1.1\r\nHost: localhost\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, payload = raw.partition(b"\r\n\r\n")
    status = int(head.split()[1])
    if status != 200:
        raise RuntimeError(f"HTTP {status}: {payload[:200]!r}")
    return json.loads(payload)["choices"][0]["tokens"]


def serve_http(eng, prompts) -> list[list[int]]:
    """Serve `prompts` as concurrent HTTP completions through the engine's
    bridge thread and the asyncio frontend on localhost."""
    from repro.serve.frontend import CompletionFrontend, EngineBridge

    bridge = EngineBridge(eng)
    fe = CompletionFrontend(bridge)

    async def go():
        await fe.start()
        try:
            return await asyncio.gather(
                *[_complete(fe.port, p, NEW_TOKENS) for p in prompts])
        finally:
            await fe.stop()

    with bridge:
        streams = asyncio.run(go())
    if bridge.error is not None:
        raise RuntimeError(f"engine thread failed: {bridge.error!r}")
    return streams


def agreement(a: list[list[int]], b: list[list[int]]) -> float:
    same = sum(x == y for sa, sb in zip(a, b) for x, y in zip(sa, sb))
    return same / sum(len(s) for s in a)


def check_streams(streams, vocab: int, what: str) -> None:
    for s in streams:
        if len(s) != NEW_TOKENS or not all(0 <= t < vocab for t in s):
            raise RuntimeError(f"{what}: bad stream {s}")


def compare_logits(got: np.ndarray, want: np.ndarray, rtol: float,
                   what: str) -> float:
    """Max |got - want|; raises above `rtol` of max |want|."""
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise RuntimeError(f"{what}: logits {got.shape} vs {want.shape}, "
                           "or not finite")
    diff = float(np.max(np.abs(got - want)))
    bound = rtol * float(np.max(np.abs(want)))
    if not diff <= bound:
        raise RuntimeError(f"{what}: logits differ by {diff} > {bound}")
    return diff


def serve_phase(cfg, *, seed: int) -> dict:
    from repro.models import lm
    from repro.serve.decode import make_paged_serve_step
    from repro.serve.engine import EngineConfig, ServeEngine

    params = lm.init(cfg, jax.random.PRNGKey(seed))
    prompts = make_prompts(cfg.vocab, seed)
    log(f"[serve] {cfg.name}: {len(prompts)} prompts of "
        f"{sorted(len(p) for p in prompts)} tokens, {NEW_TOKENS} greedy "
        "new tokens each")
    ref_steps = {s: jax.jit(make_paged_serve_step(cfg, s, paged_kernel=False))
                 for s in ("quartet2", "bf16")}
    out = {}
    for kv_quant, scheme, rtol in SERVE_RUNS:
        pool = "nvfp4" if kv_quant else "bf16"
        eng = ServeEngine(cfg, params, EngineConfig(
            n_slots=N_SLOTS, max_len=MAX_LEN, kv_quant=kv_quant,
            scheme=scheme))
        if not eng.paged_kernel:
            raise RuntimeError("paged kernel not resolved on TPU")
        run = run_direct(eng, prompts,
                         None if rtol is None else ref_steps[scheme])
        check_streams(run["streams"], cfg.vocab, f"{pool} {scheme}")
        if "tpu_custom_call" not in decode_step_hlo(eng):
            raise RuntimeError(f"{pool} pool: no compiled Pallas kernel "
                               "(tpu_custom_call) in the decode step")
        msg = (f"[serve] {pool} pool, {scheme}: Pallas kernel compiled "
               f"(tpu_custom_call); {len(run['streams'])} streams of "
               f"{NEW_TOKENS} tokens")
        if rtol is not None:
            diff = compare_logits(run["first"], run["first_ref"], rtol,
                                  f"{pool} {scheme} kernel vs reference step")
            msg += ("; vs the gather-reference step on the same pool state: "
                    "greedy token agreement over all decode steps "
                    f"{run['step_agreement']}; first decode logits of every "
                    f"request max |diff| {diff}, max |ref| "
                    f"{float(np.max(np.abs(run['first_ref'])))}, bound "
                    f"{rtol} x that")
            out[pool, scheme] = {"step_agreement": run["step_agreement"],
                                 "logit_max_abs_diff": diff}
        log(msg)
        if scheme == "quartet2" and not kv_quant:
            served = serve_http(eng, prompts)
            check_streams(served, cfg.vocab, "http")
            log(f"[serve] http: {len(served)} concurrent completions "
                "over localhost, token agreement with the direct run "
                f"{agreement(served, run['streams'])}")
    return out


def device_bytes(tree, devices) -> list[int]:
    per = Counter()
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            per[shard.device.id] += shard.data.nbytes
    return [per[d.id] for d in devices]


def sharded_phase(cfg, devices, *, seed: int) -> dict:
    from repro.launch.mesh import make_serve_mesh
    from repro.models import lm
    from repro.serve.engine import EngineConfig, ServeEngine

    params = jax.device_put(lm.init(cfg, jax.random.PRNGKey(seed)),
                            devices[0])
    prompts = make_prompts(cfg.vocab, seed)
    econf = EngineConfig(n_slots=N_SLOTS, max_len=MAX_LEN, scheme="bf16")
    sharded = ServeEngine(cfg, params, dataclasses.replace(
        econf, mesh=make_serve_mesh(data=2, model=2)))
    param_b = device_bytes(sharded.params, devices)
    pool_b = device_bytes(sharded.pool.caches, devices)
    total_param = sum(x.nbytes for x in jax.tree.leaves(sharded.params))
    total_pool = sum(x.nbytes for x in jax.tree.leaves(sharded.pool.caches))
    log(f"[sharded] (data=2, model=2) bytes per device: params {param_b} "
        f"of {total_param}; pool {pool_b} of {total_pool}")
    if max(param_b) >= total_param or any(b != total_pool // 2
                                          for b in pool_b):
        raise RuntimeError("weights not split over 'model' or pool blocks "
                           "not split over 'data'")
    got = run_direct(sharded, prompts)
    if "tpu_custom_call" not in decode_step_hlo(sharded):
        raise RuntimeError("no compiled Pallas kernel in the sharded step")
    want = run_direct(ServeEngine(cfg, params, econf), prompts)
    check_streams(got["streams"], cfg.vocab, "sharded")
    check_streams(want["streams"], cfg.vocab, "one chip")
    agree = agreement(got["streams"], want["streams"])
    diff = compare_logits(got["first"], want["first"], SHARDED_LOGIT_RTOL,
                          "sharded vs one chip")
    log(f"[sharded] vs the one-chip engine: token agreement {agree}; first "
        f"decode logits of every request max |diff| {diff}, max |ref| "
        f"{float(np.max(np.abs(want['first'])))}, bound "
        f"{SHARDED_LOGIT_RTOL} x that")
    return {"token_agreement": agree, "logit_max_abs_diff": diff,
            "param_bytes": param_b, "pool_bytes": pool_b}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the mesh-sharded serving phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = device_gate(args.chips)
    from repro.configs import registry
    from repro.launch.compile_cache import configure_compile_cache
    log(f"compile cache: {configure_compile_cache()}")
    cfg = registry.get(ARCH)
    if args.chips == 4:
        sharded_phase(cfg, devices, seed=args.seed)
    else:
        train_phase(cfg, seq=TRAIN_SEQ, batch=TRAIN_BATCH, steps=TRAIN_STEPS,
                    seed=args.seed)
        stats = devices[0].memory_stats() or {}
        log(f"[train] peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
        kernel_phase(cfg, seed=args.seed)
        serve_phase(cfg, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
