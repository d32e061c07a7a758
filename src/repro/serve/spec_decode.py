"""Self-speculative decoding with exact bitwise verification.

The Quartet II NVFP4 forward is DETERMINISTIC (quantize-once PackedQWeight
weights, RTN/4-over-6 activation quantizers, fixed seed), so speculation can
be validated exactly instead of statistically: a truncated-stack draft — the
first `draft_layers` blocks of the SAME model plus the shared LM head, no
second set of weights — proposes K tokens per slot, and one batched
(n_slots, K+1) chunk through the engine's existing chunked decode path
verifies every position. Accepted tokens are, by construction, exactly the
tokens the full model would emit greedily one at a time.

Round structure (all device calls batched over the fixed slot set):

  1. CATCH-UP   — the draft consumes committed tokens it has not seen yet
                  (it always trails the full model by >= 1 token after a
                  fully-accepted round).
  2. PROPOSE    — K single-token draft steps; each argmax feeds the next.
  3. VERIFY     — one full-model chunk over [last_tok, d_1 .. d_K]; logits
                  at chunk index j are the model's prediction for position
                  pos+j+1, so target t_{j+1} = argmax(logits[:, j]).
  4. ACCEPT     — greedy requests keep the longest prefix with d_j == t_j,
                  then emit one more model token for free (the correction /
                  bonus). Stochastic requests rejection-sample each draft
                  against the verify chunk's target distribution
                  (serve/sampling.py `speculative_resample`): the emitted
                  tokens follow the target sampling law exactly, with
                  per-(round, slot) keys keeping streams reproducible.
  5. ROLLBACK   — rejected positions are logically truncated: token caches
                  (kv / mla) need no physical undo (stale entries hide
                  behind the position mask until overwritten); recurrent
                  state (wkv / tm_prev / cm_prev / lru) integrated the whole
                  chunk, so it is restored from a pre-verify snapshot and
                  the committed prefix is replayed through the engine's
                  (n_slots, 1) step. Archs without recurrent state pay no
                  replay at all.

Numerics note: bitwise equality of the emitted stream with the
non-speculative engine requires the per-row forward to be chunk-size
invariant. That holds exactly for bf16 (row-independent arithmetic) and for
rwkv below the chunked-WKV threshold (cfg.rwkv.chunk); quantizing schemes
share one activation absmax across the (slots x chunk) tensor, so quartet2
streams are deterministic run-to-run but can differ from the S=1 engine in
near-tie argmaxes. tests/test_spec_decode.py pins both properties.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.serve.kv_pool import KVPool
from repro.serve.sampling import accept_greedy, greedy_targets

_SEED = jnp.array([7, 7], jnp.uint32)  # deterministic forward; see decode.py


def _blank(n_slots: int, size: int = 1):
    return (np.zeros((n_slots, size), np.int32),
            np.zeros((n_slots,), np.int32),
            np.zeros((n_slots,), bool))


class DraftStack:
    """The truncated-stack draft: prefix forward + its own KV pool.

    Reuses the engine's (possibly prequantized) params by slicing the
    stacked layer leaves — the draft never owns weights. Its pool covers
    only the prefix layers' cache kinds, with the same paged/dense layout
    and slot count as the main pool so slot indices line up."""

    def __init__(self, cfg, params, econf):
        self.cfg = cfg
        self.econf = econf
        self.n_prefix = econf.draft_layers
        self.specs = lm.prefix_specs(cfg, econf.draft_layers)  # validates
        self.paged_kernel = econf.resolved_paged_kernel()
        e = econf
        self.mesh = e.mesh
        shards = (dict(self.mesh.shape).get("data", 1)
                  if self.mesh is not None else 1)
        self.pool = KVPool(cfg, e.n_slots, e.max_len, paged=e.paged,
                           block_size=e.block_size, n_blocks=e.n_blocks,
                           specs=self.specs, n_shards=shards)
        if self.mesh is not None:
            from repro.dist import sharding as SH
            self.pool.caches = jax.device_put(
                self.pool.caches,
                SH.serve_cache_shardings(self.pool.caches, self.mesh))
        self.params = params  # engine-owned; already mesh-placed when sharded
        self._step_fns: dict[int, object] = {}
        self._propose_fns: dict[int, object] = {}

    def _wrap(self, fn, *, out_batch_axis: int = 0):
        """Mesh mode: the draft's steps run under the same shard_map as the
        engine's, manual over "data" only (serve/decode.py)."""
        if self.mesh is None:
            return fn
        from repro.serve.decode import shard_serve_step
        return shard_serve_step(fn, self.mesh, out_batch_axis=out_batch_axis)

    def propose(self, k: int, tok0, pos, active):
        """K greedy proposals in ONE device call.

        A lax.scan over single-token prefix steps keeps the whole
        propose-argmax-feed-back loop on device: one dispatch and one host
        sync per round instead of K. tok0/pos/active: (n_slots,) — each
        active row starts from its last emitted token at its own position.
        Returns np (k, n_slots) proposed ids; the draft cache advances k
        positions for active rows."""
        fn = self._propose_fns.get(k)
        if fn is None:
            cfg, scheme, npfx = self.cfg, self.econf.scheme, self.n_prefix
            pk = self.paged_kernel

            def prop_fn(params, caches, table, tok0, pos, active):
                def body(carry, t):
                    caches, cur = carry
                    logits, caches, _ = lm.forward_prefix(
                        params, cfg, {"tokens": cur[:, None]}, scheme, _SEED,
                        n_prefix=npfx, caches=caches, mode="decode",
                        pos=pos + t, active=active, block_table=table,
                        paged_kernel=pk)
                    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                    return (caches, nxt), nxt

                (caches, _), toks = jax.lax.scan(
                    body, (caches, tok0), jnp.arange(k))
                return toks, caches

            fn = self._propose_fns[k] = jax.jit(
                self._wrap(prop_fn, out_batch_axis=1), donate_argnums=(1,))
        toks, self.pool.caches = fn(
            self.params, self.pool.caches, self.pool.table_device(),
            jnp.asarray(tok0, jnp.int32), jnp.asarray(pos),
            jnp.asarray(active))
        return np.asarray(toks)

    def forward(self, size: int, tokens, pos, active):
        fn = self._step_fns.get(size)
        if fn is None:
            cfg, scheme, npfx = self.cfg, self.econf.scheme, self.n_prefix
            pk = self.paged_kernel

            def step_fn(params, caches, table, tokens, pos, active):
                logits, caches, _ = lm.forward_prefix(
                    params, cfg, {"tokens": tokens}, scheme, _SEED,
                    n_prefix=npfx, caches=caches, mode="decode", pos=pos,
                    active=active, block_table=table, paged_kernel=pk)
                return logits, caches

            fn = self._step_fns[size] = jax.jit(
                self._wrap(step_fn), donate_argnums=(1,))
        logits, self.pool.caches = fn(
            self.params, self.pool.caches, self.pool.table_device(),
            jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(active))
        return logits


def spec_round(eng, dec: list[int]) -> int:
    """One speculative tick over the DECODE slots; returns tokens emitted.

    Mutates engine slots, both pools, and stats. Every compiled shape it
    uses is fixed per engine: the draft (n_slots, 1) step, the full-model
    (n_slots, spec_k + 1) verify chunk, and the existing (n_slots, 1) step
    for state replay."""
    e, K = eng.econf, eng.econf.spec_k
    slots, draft, pool = eng.slots, eng.draft, eng.pool

    # ---- 1. catch-up: feed the draft every committed-but-unseen token ----
    while True:
        lag = [i for i in dec if slots[i].draft_len < slots[i].length]
        if not lag:
            break
        tokens, pos, active = _blank(e.n_slots)
        for i in lag:
            s = slots[i]
            stream = s.req.prompt + s.generated
            tokens[i, 0] = stream[s.draft_len]
            pos[i] = s.draft_len
            active[i] = True
            draft.pool.ensure(i, s.draft_len + 1)
        draft.forward(1, tokens, pos, active)
        for i in lag:
            slots[i].draft_len += 1

    # ---- 2. propose: K draft tokens in one fused device call ------------
    dsnap = draft.pool.snapshot_states()
    tok0 = np.zeros((e.n_slots,), np.int32)
    pos = np.zeros((e.n_slots,), np.int32)
    active = np.zeros((e.n_slots,), bool)
    for i in dec:
        tok0[i] = slots[i].last_tok
        pos[i] = slots[i].length
        active[i] = True
        draft.pool.ensure(i, slots[i].length + K)
    toks = draft.propose(K, tok0, pos, active)        # (K, n_slots)
    proposals = {i: [int(toks[t, i]) for t in range(K)] for i in dec}
    for i in dec:
        slots[i].draft_len += K

    # ---- 3. verify: one (n_slots, K+1) full-model chunk ------------------
    snap = pool.snapshot_states()
    tokens = np.zeros((e.n_slots, K + 1), np.int32)
    pos = np.zeros((e.n_slots,), np.int32)
    active = np.zeros((e.n_slots,), bool)
    for i in dec:
        s = slots[i]
        tokens[i] = [s.last_tok] + proposals[i]
        pos[i] = s.length
        active[i] = True
        pool.ensure(i, s.length + K + 1)
    logits = eng._forward(K + 1, tokens, pos, active)
    targets = np.asarray(greedy_targets(logits))

    # ---- 4. accept (greedy or rejection-sampled) + commit ----------------
    emitted = 0
    reject_state: list[int] = []
    replay: dict[int, list[int]] = {}
    draft_reject: list[int] = []
    for i in dec:
        s = slots[i]
        length0 = s.length
        temp = s.req.sampling.temperature
        if temp == 0.0:
            a = accept_greedy(proposals[i], targets[i])
            emit = [int(targets[i, j]) for j in range(a + 1)]
        else:
            # stochastic request: rejection-sample against the verify
            # chunk's target distributions (greedy deterministic drafts =
            # point-mass proposals; see sampling.speculative_resample).
            # Token-by-token the emitted stream follows exactly the
            # distribution the non-speculative sampler draws from, though
            # the realized stream differs (different PRNG consumption).
            toks, cnt = eng._resample(
                jnp.asarray(proposals[i], jnp.int32),
                logits[i].astype(jnp.float32), eng._spec_key(i),
                temp, s.req.sampling.top_k)
            cnt = int(cnt)
            toks = np.asarray(toks)
            emit = [int(toks[j]) for j in range(cnt)]
            a = cnt - 1  # accepted drafts; the last emission is the
            #              resample / bonus token
        remaining = s.req.max_new - len(s.generated)
        emit = emit[:remaining]
        nacc = len(emit)
        # acceptance-rate accounting counts only drafts the verifier could
        # USE: on a request's final round max_new truncation caps usable
        # drafts at remaining - 1, and booking the rest as rejections would
        # bias the reported rate low even for a perfect draft
        eng.stats["draft_tokens"] += min(K, remaining - 1)
        eng.stats["accepted_tokens"] += nacc - 1
        if eng.obs.enabled:
            eng.obs.spec_accepted_hist.observe(float(nacc - 1))
        emitted += nacc
        s.generated.extend(emit)
        s.length = length0 + nacc
        s.last_tok = emit[-1]
        pool.truncate(i, s.length)
        if pool.has_state_kinds and nacc < K + 1:
            # the chunk integrated rejected inputs into wkv/lru state
            reject_state.append(i)
            replay[i] = [int(tokens[i, j]) for j in range(nacc)]
        if len(s.generated) >= s.req.max_new:
            continue  # retires next tick; its draft slot is released there
        if a >= K - 1:
            # every input the draft consumed (t0, d_1..d_{K-1}) was committed
            s.draft_len = length0 + K
        elif draft.pool.has_state_kinds:
            # draft state integrated rejected inputs: full rollback, the
            # restored snapshot is replayed by next round's catch-up
            draft_reject.append(i)
            s.draft_len = length0
            draft.pool.truncate(i, length0)
        else:
            # stateless draft caches keep the committed-correct prefix
            # (inputs t0, d_1..d_a ARE the emitted stream), so the next
            # round starts with zero catch-up work
            s.draft_len = length0 + a + 1
            draft.pool.truncate(i, length0 + a + 1)
    if draft_reject:
        draft.pool.restore_states(dsnap, draft_reject)

    # ---- 5. restore + replay recurrent state of rejected slots ----------
    if reject_state:
        pool.restore_states(snap, reject_state)
        for t in range(max(len(replay[i]) for i in reject_state)):
            tokens, pos, active = _blank(e.n_slots)
            for i in reject_state:
                if t >= len(replay[i]):
                    continue
                tokens[i, 0] = replay[i][t]
                pos[i] = slots[i].length - len(replay[i]) + t
                active[i] = True
            eng._forward(1, tokens, pos, active)

    eng.stats["spec_rounds"] += 1
    return emitted
