"""Serve a small model through the continuous-batching engine: NVFP4 forward
(4/6), quantize-once packed weights, paged KV pool, interleaved chunked
prefill + batched decode.

    PYTHONPATH=src python examples/serve_decode.py [--arch llama_200m] \
        [--reduced] [--tokens 32]

Serves the registered configuration at its published width; `--reduced`
swaps in the small same-family variant (what a CPU run wants).

`--legacy` runs the old fixed-batch greedy loop instead (the baseline the
benchmark compares against). `--data-shards N` serves through the
mesh-sharded engine (slot-affine pool over a (data=N, model=1) mesh),
simulating N host-platform devices on CPU.
"""

import argparse
import os
import sys
import time

def _early_data_shards(argv):
    """--data-shards value, read BEFORE the first jax import (jax locks the
    device count at init). Handles both '--data-shards N' and
    '--data-shards=N'; malformed values fall through to argparse's error."""
    for i, a in enumerate(argv):
        try:
            if a == "--data-shards" and i + 1 < len(argv):
                return int(argv[i + 1])
            if a.startswith("--data-shards="):
                return int(a.split("=", 1)[1])
        except ValueError:
            return 1
    return 1


_n = _early_data_shards(sys.argv)
if _n > 1 and "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_n}").strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch.compile_cache import configure_compile_cache
from repro.models import lm
from repro.serve.decode import greedy_generate
from repro.serve.engine import EngineConfig, Request, ServeEngine
from repro.serve.sampling import SamplingParams


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama_200m")
    ap.add_argument("--reduced", action="store_true",
                    help="small same-family variant of the architecture")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--scheme", default="quartet2")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--legacy", action="store_true",
                    help="seed fixed-batch greedy loop (baseline)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative tokens per round (0 = off); the "
                         "emitted greedy stream is bitwise unchanged")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="truncated-stack draft depth (default: half the "
                         "stack when --spec-k > 0)")
    ap.add_argument("--no-prequant", action="store_true")
    ap.add_argument("--dense", action="store_true",
                    help="dense per-slot caches instead of the paged pool")
    ap.add_argument("--paged-kernel", default=None,
                    choices=["on", "off"],
                    help="block-table flash-decode Pallas kernel "
                         "(default: on for TPU, off for CPU where it would "
                         "run interpreted; 'on' forces interpret mode)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="store the paged KV pool as packed NVFP4 "
                         "(PackedKV: e2m1 codes + e4m3 group scales, "
                         "0.28125x bf16 bytes; dequantized in-kernel or "
                         "exactly on the gather path — see serve/README "
                         "'Quantized KV cache')")
    ap.add_argument("--data-shards", type=int, default=1,
                    help="serve through the mesh-sharded engine: slots + "
                         "slot-affine KV pool over a (data=N, model=1) mesh "
                         "(greedy streams stay bitwise identical in bf16)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="serve N requests over ONE shared system prompt "
                         "through the radix prefix cache "
                         "(serve/prefix_cache.py): a warmup request primes "
                         "the cache, then the N requests alias its blocks "
                         "read-only and skip that prefill — reports prefill "
                         "tokens skipped and the hit rate")
    ap.add_argument("--spill-tier", type=int, default=0, metavar="N",
                    help="hierarchical-cache demo: prime a shared system "
                         "prompt, squeeze it out of the pool with filler "
                         "traffic, then serve N requests over it — with "
                         "the host spill tier the eviction snapshots the "
                         "blocks to host RAM and the N requests swap them "
                         "back in (zero prefill forwards over the prefix); "
                         "reports spill/swap-in/replication stats next to "
                         "the drop-on-evict baseline (compose with "
                         "--data-shards 2 to see cross-shard replication)")
    ap.add_argument("--metrics", action="store_true",
                    help="run with the observability layer enabled "
                         "(obs/instrumentation.py): report TTFT/queue-wait "
                         "percentiles and print the Prometheus-text metrics "
                         "snapshot at exit")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="dump per-request trace spans as JSONL "
                         "(implies --metrics)")
    args = ap.parse_args()

    configure_compile_cache()
    backend = jax.default_backend().upper()
    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    b, s = args.batch, args.prompt_len
    rng = np.random.RandomState(1)
    if args.shared_prefix > 0:
        return shared_prefix_demo(cfg, params, args, rng, backend)
    if args.spill_tier > 0:
        return spill_tier_demo(cfg, params, args, rng, backend)
    prompts = [list(map(int, rng.randint(0, cfg.vocab, s))) for _ in range(b)]

    if args.legacy:
        t0 = time.perf_counter()
        gen = greedy_generate(params, cfg, args.scheme, jnp.asarray(prompts),
                              args.tokens)
        jax.block_until_ready(gen)
        dt = time.perf_counter() - t0
        print(f"arch={cfg.name} scheme={args.scheme} legacy loop")
        print(f"generate: {b}x{args.tokens} tokens in {dt*1e3:.0f}ms "
              f"= {b*args.tokens/dt:.1f} tok/s ({backend})")
        print("sample token ids:", gen[0, :12].tolist())
        return

    draft_layers = args.draft_layers
    if args.spec_k > 0 and draft_layers == 0:
        from repro.models.lm import total_layers
        draft_layers = max(1, total_layers(cfg) // 2)
    max_len = ((s + args.tokens + args.spec_k) // 16 + 2) * 16
    mesh = None
    if args.data_shards > 1:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(args.data_shards, 1)
    obs = None
    if args.metrics or args.trace_out:
        from repro.obs import Instrumentation, MetricsRegistry
        obs = Instrumentation(registry=MetricsRegistry())
    eng = ServeEngine(cfg, params, EngineConfig(
        n_slots=b, max_len=max_len, prefill_chunk=16,
        paged=not args.dense, prequant=not args.no_prequant,
        kv_quant=args.kv_quant,
        scheme=args.scheme, spec_k=args.spec_k, draft_layers=draft_layers,
        paged_kernel=(None if args.paged_kernel is None
                      else args.paged_kernel == "on"), mesh=mesh, obs=obs))
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k)
    ids = [eng.submit(Request(prompt=p, max_new=args.tokens, sampling=sp))
           for p in prompts]
    t0 = time.perf_counter()
    results = {r.req_id: r for r in eng.run()}
    wall = time.perf_counter() - t0
    st = eng.stats

    print(f"arch={cfg.name} scheme={args.scheme} engine "
          f"(paged={not args.dense}, prequant={not args.no_prequant}, "
          f"paged_kernel={eng.paged_kernel}"
          + (", kv_quant=True" if args.kv_quant else "")
          + (f", data_shards={eng.data_shards}" if mesh is not None else "")
          + ")")
    print(f"prefill: {st['prefill_tokens']} tokens in {st['prefill_s']*1e3:.0f}ms")
    print(f"decode:  {st['decode_tokens']} tokens over {st['decode_steps']} "
          f"steps = {st['decode_tokens']/max(st['decode_s'],1e-9):.1f} tok/s "
          f"({backend})")
    if args.spec_k > 0:
        acc = st["accepted_tokens"] / max(st["draft_tokens"], 1)
        print(f"spec:    {st['spec_rounds']} rounds, spec_k={args.spec_k}, "
              f"draft_layers={draft_layers}, "
              f"accepted {st['accepted_tokens']}/{st['draft_tokens']} "
              f"drafts (rate {acc:.2f})")
    print(f"end-to-end: {wall*1e3:.0f}ms, slots={b}, "
          f"pool blocks free {eng.pool.free_block_count}/{eng.pool.n_blocks}")
    print("sample token ids:", results[ids[0]].tokens[:12])

    if obs is not None:
        agg = obs.trace_sink.aggregates()
        for name, label in (("queue_wait_s", "queue wait"),
                            ("ttft_s", "TTFT"),
                            ("decode_tok_s", "decode/token")):
            p = agg[name]
            if p.get("count"):
                print(f"{label}: p50 {p['p50']*1e3:.1f}ms "
                      f"p95 {p['p95']*1e3:.1f}ms p99 {p['p99']*1e3:.1f}ms "
                      f"(n={p['count']})")
        if args.trace_out:
            n = obs.trace_sink.write_jsonl(args.trace_out)
            print(f"wrote {n} trace events "
                  f"({len(obs.trace_sink.traces)} requests) to "
                  f"{args.trace_out}")
        print("--- metrics snapshot (Prometheus text) ---")
        print(obs.prometheus(), end="")


def shared_prefix_demo(cfg, params, args, rng, backend):
    """--shared-prefix N: N requests over one system prompt.

    One warmup request primes the radix cache with the shared prompt's
    blocks; the N follow-ups each append a short unique suffix, alias the
    cached prefix read-only (skipping its prefill entirely), and COW at the
    divergence. Reported: prefill tokens skipped, cache hit rate, and the
    prefill-time delta vs a cold (cache-off) engine on the same workload."""
    n, s = args.shared_prefix, args.prompt_len
    system = list(map(int, rng.randint(0, cfg.vocab, s)))
    suffix = 4
    prompts = [system + list(map(int, rng.randint(0, cfg.vocab, suffix)))
               for _ in range(n)]
    # unrelated warmup prompt: triggers every step-shape jit compile in
    # BOTH engines before the timed region (otherwise the cold engine pays
    # compile time inside its wall and the "speedup" is mostly XLA)
    warm = list(map(int, rng.randint(0, cfg.vocab, 17)))
    max_len = ((s + suffix + args.tokens) // 16 + 2) * 16

    def serve(prefix_cache):
        eng = ServeEngine(cfg, params, EngineConfig(
            n_slots=min(4, n), max_len=max_len, prefill_chunk=16,
            prequant=not args.no_prequant, scheme=args.scheme,
            prefix_cache=prefix_cache))
        eng.submit(Request(prompt=list(warm), max_new=2))
        eng.run()
        if prefix_cache:
            eng.submit(Request(prompt=list(system), max_new=1))  # prime
            eng.run()
        for k in eng.stats:
            eng.stats[k] = 0 if isinstance(eng.stats[k], int) else 0.0
        if eng.cache is not None:  # hit rate measures the N requests only
            for k in eng.cache.stats:
                eng.cache.stats[k] = 0
        t0 = time.perf_counter()
        for p in prompts:
            eng.submit(Request(prompt=p, max_new=args.tokens))
        results = eng.run()
        return eng, time.perf_counter() - t0, results

    cold_eng, cold_wall, _ = serve(False)
    hot_eng, hot_wall, results = serve(True)
    st = hot_eng.stats
    cst = hot_eng.cache.stats if hot_eng.cache else {}
    print(f"arch={cfg.name} scheme={args.scheme} shared-prefix demo "
          f"({n} requests x [{s} shared + {suffix} unique] tokens, "
          f"{backend})")
    print(f"cold engine: prefill {cold_eng.stats['prefill_tokens']} tokens, "
          f"wall {cold_wall*1e3:.0f}ms")
    print(f"hot engine:  prefill {st['prefill_tokens']} tokens "
          f"({st['prefill_skipped_tokens']} skipped via "
          f"{st['prefix_hits']} prefix hits), wall {hot_wall*1e3:.0f}ms")
    if cst:
        hit_rate = cst["hits"] / max(cst["lookups"], 1)
        print(f"cache: {cst['hits']}/{cst['lookups']} lookups hit "
              f"(rate {hit_rate:.2f}), {cst['hit_tokens']} tokens matched, "
              f"{cst['inserted_blocks']} blocks newly cached this wave, "
              f"{cst['evicted_blocks']} evicted")
    print("sample token ids:", results[0].tokens[:12])


def spill_tier_demo(cfg, params, args, rng, backend):
    """--spill-tier N: a hot prefix is evicted under pool pressure, then
    reused N times.

    A prime request caches the shared system prompt; filler traffic then
    squeezes the pool until the prefix's blocks are evicted. In drop mode
    (prefix_spill=False, the baseline) the N follow-ups recompute the
    prefix from scratch; with the host tier ON the eviction spilled the
    bytes to host RAM, the nodes stayed matchable, and the follow-ups swap
    them back in — zero prefill forwards over the matched prefix. With
    --data-shards >= 2 the repeated hits also replicate the prefix into
    peer shards (replicate_hits=2). Reported per mode: prefill tokens and
    skips, plus the spill / swap-in / replication counters and the
    host-tier byte count (serve/README 'Hierarchical cache &
    disaggregation')."""
    n, s = args.spill_tier, args.prompt_len
    system = list(map(int, rng.randint(0, cfg.vocab, s)))
    suffix, new = 4, min(args.tokens, 12)
    prompts = [system + list(map(int, rng.randint(0, cfg.vocab, suffix)))
               for _ in range(n)]
    # enough distinct retired streams to overflow the 2*max_len/8-block
    # pool and force the primed prefix out (LRU: it is the oldest node)
    fillers = [list(map(int, rng.randint(0, cfg.vocab, s + 8)))
               for _ in range(4)]
    warm = list(map(int, rng.randint(0, cfg.vocab, 17)))
    max_len = ((s + suffix + new) // 16 + 2) * 16
    mesh = None
    if args.data_shards > 1:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(args.data_shards, 1)

    def serve(spill):
        eng = ServeEngine(cfg, params, EngineConfig(
            n_slots=2, max_len=max_len, prefill_chunk=16, block_size=8,
            prequant=not args.no_prequant, scheme=args.scheme,
            prefix_cache=True, prefix_spill=spill,
            replicate_hits=2 if spill else None, mesh=mesh))
        eng.submit(Request(prompt=list(warm), max_new=2))  # jit warmup
        eng.run()
        eng.submit(Request(prompt=list(system), max_new=1))  # prime
        eng.run()
        for _ in range(2):  # two hot hits arm cross-shard replication
            eng.submit(Request(prompt=list(system), max_new=1))
            eng.run()
        for f in fillers:  # pool pressure: evicts (or spills) the prefix
            eng.submit(Request(prompt=list(f), max_new=4))
            eng.run()
        spilled = eng.cache.stats["spilled_blocks"]
        replicated = eng.cache.stats["replicated_blocks"]
        for st in (eng.stats, eng.cache.stats):
            for k in st:
                st[k] = 0 if isinstance(st[k], int) else 0.0
        t0 = time.perf_counter()
        for p in prompts:
            eng.submit(Request(prompt=list(p), max_new=new))
        results = eng.run()
        return eng, spilled, replicated, time.perf_counter() - t0, results

    drop_eng, _, _, drop_wall, _ = serve(False)
    hot_eng, spilled, replicated, hot_wall, results = serve(True)
    st, cst = hot_eng.stats, hot_eng.cache.stats
    dst, dcst = drop_eng.stats, drop_eng.cache.stats
    print(f"arch={cfg.name} scheme={args.scheme} spill-tier demo "
          f"({n} requests x [{s} shared + {suffix} unique] tokens over an "
          f"evicted prefix, {backend}"
          + (f", data_shards={hot_eng.data_shards}" if mesh else "") + ")")
    print(f"drop mode:  prefill {dst['prefill_tokens']} tokens "
          f"({dst['prefill_skipped_tokens']} skipped, "
          f"{dcst['hits']}/{dcst['lookups']} lookups hit), "
          f"wall {drop_wall*1e3:.0f}ms — the evicted prefix recomputes")
    print(f"spill mode: prefill {st['prefill_tokens']} tokens "
          f"({st['prefill_skipped_tokens']} skipped, "
          f"{cst['hits']}/{cst['lookups']} lookups hit), "
          f"wall {hot_wall*1e3:.0f}ms")
    print(f"host tier:  {spilled} blocks spilled under pressure, "
          f"{cst['swapped_in_blocks']} swapped back in "
          f"({cst['swapin_s']*1e3:.1f}ms dispatch, overlapped with decode), "
          f"{replicated + cst['replicated_blocks']} replicated to peer "
          f"shards, {hot_eng.cache.host_bytes} bytes resident on host")
    print("sample token ids:", results[0].tokens[:12])


if __name__ == "__main__":
    main()
