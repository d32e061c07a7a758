"""Compile rehearsal for TPU v5e: the Pallas paged-attention kernels and the
mesh-sharded serving step, compiled for a DESCRIBED v5e:2x2 topology.

Interpret mode (every other kernel test) cannot see what Mosaic refuses —
unsupported shape casts, tiling, VMEM — nor a kernel GSPMD would have to
partition. These tests compile the real programs at real widths for the
chip without one attached: nothing runs, so they check only that the
compiler accepts each program and that the kernel is in it
(`tpu_custom_call`).

The topology is described inside a module-scoped fixture (never at import
or collection time): only one process may load the TPU library, and every
pytest-xdist worker imports this file. Where it cannot be described, the
fixture skips. The persistent compilation cache is off around these
compiles: an entry written for a described chip cannot be read back here.
"""

import os

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
    if packed:
        pool = [S((P_BLOCKS, BS, GQA_KV, GQA_HD // 2), jnp.uint8),
                S((P_BLOCKS, BS, GQA_KV, GQA_HD // F.GROUP), jnp.uint8)] * 2
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
    cannot partition over "model" — is compiled in."""
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
