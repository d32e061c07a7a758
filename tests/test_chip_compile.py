"""Compile rehearsal for TPU v5e: the Pallas paged-attention kernels and the
mesh-sharded serving step, compiled for a DESCRIBED v5e:2x2 topology.

Interpret mode (every other kernel test) cannot see what Mosaic refuses —
unsupported shape casts, tiling, VMEM — nor a kernel GSPMD would have to
partition. These tests compile the real programs at real widths for the
chip without one attached: nothing runs, so they check only that the
compiler accepts each program and that the kernel is in it
(`tpu_custom_call`), and, for the one-chip engine step, that the compiled
program updates the KV pool in place (no pool-sized copy or temporary).

The topology is described inside a module-scoped fixture (never at import
or collection time): only one process may load the TPU library, and every
pytest-xdist worker imports this file. Where it cannot be described, the
fixture skips. The persistent compilation cache is off around these
compiles: an entry written for a described chip cannot be read back here.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.core import formats as F
from repro.kernels import ops

# paged pool geometry the engine uses: 16-token blocks; a 1024-token table
P_BLOCKS, BS, B, MAXB = 64, 16, 8, 64
# llama-200m attention (h = kv = 10, head dim 128) and DeepSeek-V3 latents
GQA_H, GQA_KV, GQA_HD = 10, 10, 128
MLA_H, MLA_LORA, MLA_ROPE = 16, 512, 64
# the engine step at Granite-8B's attention (32 heads over 8 KV heads x 128)
# in 4 layers whose other widths are small, so the pool is most of what the
# step holds: 512 pool blocks, 16 slots of 4096 tokens
STEP_LAYERS, STEP_BLOCKS, STEP_SLOTS, STEP_LEN = 4, 512, 16, 4096
# ops that move a whole array: a fusion is named after the ops it holds
_MOVES = ("copy", "copy-start", "copy-done", "dynamic-slice",
          "dynamic-update-slice")
_COLLECTIVES = ("all-gather", "all-gather-start", "all-reduce",
                "all-reduce-start", "all-to-all", "collective-permute",
                "collective-permute-start")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _gqa_args(one_chip, sq, packed):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = S((B, sq, GQA_H, GQA_HD), jnp.bfloat16)
    if packed:   # kv_pool.PackedKVPair: K and V in one pair of leaves
        pool = [S((P_BLOCKS, BS, GQA_KV, GQA_HD), jnp.uint8),
                S((P_BLOCKS, GQA_KV, 2 * BS * GQA_HD // F.GROUP), jnp.uint8)]
    else:
        pool = [S((P_BLOCKS, BS, GQA_KV, GQA_HD), jnp.bfloat16)] * 2
    return q, pool, S((B, MAXB), jnp.int32), S((B,), jnp.int32)


@pytest.mark.parametrize("window", [None, 2048])
@pytest.mark.parametrize("sq", [1, 16])
@pytest.mark.parametrize("packed", [False, True], ids=["bf16", "nvfp4"])
def test_gqa_kernel_compiles(one_chip, packed, sq, window):
    q, pool, table, pos = _gqa_args(one_chip, sq, packed)
    kernel = ops.paged_attention_q if packed else ops.paged_attention
    text = _compile_text(
        lambda *a: kernel(*a, window=window, interpret=False),
        q, *pool, table, pos)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sq", [1, 16])
@pytest.mark.parametrize("packed", [False, True], ids=["bf16", "nvfp4"])
def test_mla_kernel_compiles(one_chip, packed, sq):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    qa = S((B, sq, MLA_H, MLA_LORA), jnp.bfloat16)
    qr = S((B, sq, MLA_H, MLA_ROPE), jnp.bfloat16)
    if packed:
        pool = [S((P_BLOCKS, BS, d // 2), jnp.uint8) if i == 0 else
                S((P_BLOCKS, BS, d // F.GROUP), jnp.uint8)
                for d in (MLA_LORA, MLA_ROPE) for i in (0, 1)]
        kernel = ops.paged_mla_attention_q
    else:
        pool = [S((P_BLOCKS, BS, d), jnp.bfloat16)
                for d in (MLA_LORA, MLA_ROPE)]
        kernel = ops.paged_mla_attention
    text = _compile_text(
        lambda *a: kernel(*a, qk_dim=192, interpret=False),
        qa, qr, *pool, S((B, MAXB), jnp.int32), S((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "nvfp4"])
def test_sharded_serve_step_compiles(topo, kv_quant, monkeypatch):
    """The engine's decode step for llama-200m on a (data=2, model=2) mesh:
    the layer scan stays a while loop, and the Pallas kernel — which GSPMD
    cannot partition over "model" — is compiled in. The pool, replicated
    over "model", stays whole through the scan that carries it: no
    collective moves a pool-shaped array (split over "model", a written
    pool would be gathered back whole for the kernel at every layer)."""
    from repro.dist import sharding as SH
    from repro.models import lm
    from repro.serve import decode as D
    from repro.serve.kv_pool import KVPool
    from repro.serve.prequant import prequantize

    # the model code resolves interpret mode from the (CPU) backend
    monkeypatch.setattr(ops, "_resolve_interpret", lambda interpret: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    cfg = registry.get("llama_200m")
    n_slots, max_len = 8, 512

    def placed(tree, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings)

    params = jax.eval_shape(lambda: prequantize(
        lm.init(cfg, jax.random.PRNGKey(0)), cfg, "quartet2"))
    pool = jax.eval_shape(lambda: KVPool(cfg, n_slots, max_len, n_shards=2,
                                         quantized=kv_quant).caches)
    rows = NamedSharding(mesh, P("data"))
    step = D.make_sharded_serve_step(cfg, "quartet2", mesh, paged_kernel=True)
    text = _compile_text(
        step, placed(params, SH.serve_param_shardings(params, mesh)),
        placed(pool, SH.serve_cache_shardings(pool, mesh)),
        jax.ShapeDtypeStruct((n_slots, 2, max_len // BS), jnp.int32,
                             sharding=rows),
        jax.ShapeDtypeStruct((n_slots, 1), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((n_slots,), jnp.bool_, sharding=rows))
    assert "tpu_custom_call" in text
    assert "while" in text
    shapes = set()
    for a in jax.tree.leaves(pool):     # global and per-"data"-shard stacks
        local = (a.shape[0], a.shape[1] // 2, *a.shape[2:])
        shapes |= {tuple(a.shape), local, tuple(a.shape[1:]), local[1:]}
    moved = _pool_moves(text, shapes, _COLLECTIVES)
    assert not moved, moved


def _staged(line):
    """Whether a `copy-start` only stages an array between the chip's
    memory spaces (HBM and on-core memory, `S(n)` in its layout) with the
    same layout: the compiler's placement of an array small enough to fit
    on the core, not a relayout or a write-back."""
    lays = re.findall(r"\[[\d,]*\]\{([^}]*)\}", line.split(" copy-start(")[0])
    return len({re.sub(r"S\(\d+\)", "", lay) for lay in lays[:2]}) == 1


def _pool_moves(text, shapes, ops=_MOVES):
    """(computation header, result shape, instruction) of every op in
    `ops` (by default copies, slices and slice updates) in the compiled
    `text` whose result has one of `shapes`. An asynchronous copy counts
    at its `copy-start`, unless it only stages the array between memory
    spaces (`_staged`)."""
    found, comp = [], ""
    for line in text.splitlines():
        if line and not line[0].isspace():
            comp = line
            continue
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \(?\w+\[([\d,]*)\].*? "
                     r"([\w-]+)\(%", line)
        if not m:
            continue
        name, dims, op = m.groups()
        shape = tuple(int(d) for d in dims.split(",")) if dims else ()
        if op == "copy-done" or (op == "copy-start" and _staged(line)):
            continue
        moved = op in ops or (op == "fusion"
                              and any(k in name for k in ops))
        if moved and shape in shapes:
            found.append((comp, shape, line.strip()))
    return found


@pytest.mark.parametrize("size", [1, 16])
@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "nvfp4"])
def test_serve_step_updates_pool_in_place(one_chip, kv_quant, size,
                                          monkeypatch):
    """The engine step (decode at size 1, a prefill chunk at 16) carries the
    donated KV pool through its layer scan and updates it in place: no
    layer's pool is sliced out, copied for the kernel or written back into
    a second stacked buffer, and the step's temporaries stay far below
    the pool (the stacked-scan-output form held 1.4 pools of them).

    The NVFP4 pool (`PackedKVPair`) is held to the same: its leaves end in
    whole 128-lane rows, so the chip stores them row-major, as the Pallas
    kernel reads them, and nothing relayouts the stack around the scan."""
    from repro.models import lm
    from repro.serve import decode as D
    from repro.serve.kv_pool import KVPool

    monkeypatch.setattr(ops, "_resolve_interpret", lambda interpret: False)
    cfg = dataclasses.replace(registry.get("granite_8b"),
                              n_layers=STEP_LAYERS, d_model=256, d_ff=512,
                              vocab=1024, head_dim=128)
    placed = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = placed(jax.eval_shape(lambda: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16),
        lm.init(cfg, jax.random.PRNGKey(0)))))
    pool = placed(jax.eval_shape(lambda: KVPool(
        cfg, STEP_SLOTS, STEP_LEN, n_blocks=STEP_BLOCKS,
        quantized=kv_quant).caches))
    leaves = jax.tree.leaves(pool)
    stacked = {tuple(a.shape) for a in leaves}
    one_layer = {s[1:] for s in stacked}
    pool_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    rows = lambda *shape, dt=jnp.int32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    step = D.make_paged_serve_step(cfg, "bf16", paged_kernel=True)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, rows(STEP_SLOTS, 2, STEP_LEN // BS),
        rows(STEP_SLOTS, size), rows(STEP_SLOTS),
        rows(STEP_SLOTS, dt=jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "while" in text

    moves = _pool_moves(text, stacked | one_layer)
    assert not moves, moves
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 4, (temp, pool_bytes)
