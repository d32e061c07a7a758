"""Serve over HTTP: the asyncio streaming frontend end-to-end.

    PYTHONPATH=src python examples/serve_http.py [--arch llama_200m] \
        [--reduced] [--tokens 24]

Serves the registered configuration at its published width; `--reduced`
swaps in the small same-family variant (what a CPU run wants).

Spawns the engine on its bridge thread behind `CompletionFrontend`
(serve/frontend.py), then from stdlib-asyncio clients on localhost:

  1. streams several completions concurrently over SSE;
  2. hard-kills one client mid-stream (socket RST) — the frontend cancels
     the request, the engine caches its partial prefix and reclaims its
     pool blocks;
  3. resubmits the killed prompt and shows the prefix-cache hot hit:
     the resumed stream picks up the cancelled work instead of redoing it;
  4. prints the reclaim/lifecycle stats from `GET /v1/stats`.

Everything is stdlib — no HTTP client library, no server framework.
"""

import argparse
import asyncio
import json
import time

import jax
import numpy as np

from repro.configs import registry
from repro.launch.compile_cache import configure_compile_cache
from repro.models import lm
from repro.serve.engine import EngineConfig, ServeEngine
from repro.serve.frontend import CompletionFrontend, EngineBridge, \
    FrontendConfig


async def stream(port, prompt, max_new, kill_after=None):
    """SSE client; returns (tokens, done). `kill_after` aborts the socket
    after that many tokens — the mid-stream disconnect scenario."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps({"prompt": prompt, "max_tokens": max_new,
                       "stream": True}).encode()
    writer.write((f"POST /v1/completions HTTP/1.1\r\nHost: x\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    assert status == 200, f"HTTP {status}"
    toks, done = [], False
    while True:
        line = await reader.readline()
        if not line:
            break
        if not line.startswith(b"data: "):
            continue
        payload = line[6:].strip()
        if payload == b"[DONE]":
            done = True
            break
        toks.extend(json.loads(payload)["choices"][0]["tokens"])
        if kill_after is not None and len(toks) >= kill_after:
            writer.transport.abort()  # RST, not FIN: a crashed client
            return toks, done
    writer.close()
    return toks, done


async def get_json(port, path):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
    await writer.drain()
    await reader.readline()  # status line
    while (await reader.readline()) not in (b"\r\n", b"\n", b""):
        pass
    body = await reader.read()
    writer.close()
    return json.loads(body)


async def scenario(port, prompts, max_new):
    t0 = time.perf_counter()
    results = await asyncio.gather(
        *[stream(port, p, max_new) for p in prompts[:-1]],
        stream(port, prompts[-1], max_new, kill_after=3))
    wall = time.perf_counter() - t0
    *alive, (killed_toks, _) = results
    print(f"{len(prompts)} concurrent SSE streams, one killed after "
          f"{len(killed_toks)} tokens ({wall*1e3:.0f}ms wall)")
    for i, (toks, done) in enumerate(alive):
        print(f"  stream {i}: {len(toks)} tokens, done={done}, "
              f"head={toks[:8]}")
    print(f"  stream {len(alive)} (killed): got {killed_toks}")

    # give the frontend's disconnect watcher a beat to cancel + reclaim
    for _ in range(50):
        st = await get_json(port, "/v1/stats")
        if st["stats"]["cancelled"] >= 1:
            break
        await asyncio.sleep(0.05)
    print(f"after disconnect: cancelled={st['stats']['cancelled']}, "
          f"pool free {st['pool_free_blocks']}/{st['pool_total_blocks']} "
          f"blocks, live handles={st['live_handles']}")

    # the killed stream's work survives in the prefix cache: resubmitting
    # prompt + received tokens hot-hits and decodes only the remainder
    resumed, done = await stream(port, prompts[-1] + killed_toks,
                                 max_new - len(killed_toks))
    st = await get_json(port, "/v1/stats")
    print(f"resubmit of killed prompt: +{len(resumed)} tokens (done={done}), "
          f"prefix hits={st['stats']['prefix_hits']}, "
          f"prefill skipped={st['stats']['prefill_skipped_tokens']} tokens")
    full = killed_toks + resumed
    print(f"  killed stream completed: {full}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama_200m")
    ap.add_argument("--reduced", action="store_true",
                    help="small same-family variant of the architecture")
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--scheme", default="quartet2")
    args = ap.parse_args()

    configure_compile_cache()
    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    prompts = [list(map(int, rng.randint(0, cfg.vocab, args.prompt_len)))
               for _ in range(args.clients)]
    max_len = ((args.prompt_len + args.tokens) // 16 + 2) * 16
    eng = ServeEngine(cfg, params, EngineConfig(
        n_slots=min(4, args.clients), max_len=max_len, prefill_chunk=16,
        scheme=args.scheme, prefix_cache=True))

    bridge = EngineBridge(eng)
    fe = CompletionFrontend(bridge, FrontendConfig())

    async def run():
        await fe.start()
        print(f"arch={cfg.name} scheme={args.scheme} serving on "
              f"127.0.0.1:{fe.port}")
        try:
            await scenario(fe.port, prompts, args.tokens)
        finally:
            await fe.stop()

    with bridge:
        asyncio.run(run())


if __name__ == "__main__":
    main()
