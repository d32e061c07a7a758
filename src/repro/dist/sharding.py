"""Sharding rules: parameter / optimizer-state / cache / input partition specs
for the production (data, model) mesh.

The rules are name- and shape-driven (no per-arch tables):

  - 1D params (norm gains, biases, lambdas) replicate.
  - router weights replicate (fp32, tiny, bias-sensitive — never sharded).
  - 2D weights put the out-dim on "model" when divisible, else try the in-dim;
    the in-dim additionally goes to "data" under FSDP (ZeRO-3-style).
  - stacked layer params (leading scan axis from lm._stack_init) never shard
    the leading axis; the rules above apply to the trailing dims.
  - 4D expert stacks (L, E, f, d) put experts on "model" (expert parallelism)
    and the trailing in-dim on "data" under FSDP.
  - decode caches shard batch -> "data" and head_dim -> "model"; the sequence
    axis stays unsharded (decode appends along it).

An axis is only assigned when its size divides the mesh axis size — GSPMD
would otherwise pad-and-replicate, which costs more wire than replication.

SERVING (`serve_*` below — serve/engine.py mesh mode) uses a different split
of the same mesh, because decode-step traffic is cache-dominated and the
slot-affine KV pool (serve/kv_pool.py) makes every cache access shard-local:

  - every KV-pool cache leaf shards axis 1 — the physical-BLOCK axis of
    token kinds, the SLOT axis of recurrent state / dense caches — over
    "data", never the feature axis (the decode step is manual over "data"
    via shard_map; feature-axis splits would force collectives *inside*
    each manual shard for no bandwidth win at decode batch sizes);
  - `PackedQWeight` leaves (quantize-once NVFP4 weights, core/linear.py)
    shard their out-feature axis — `packed`/`scales8` axis -2 — over
    "model"; the per-matrix `gscale` replicates. "model" stays under GSPMD
    inside the serving shard_map (it is not in its `axis_names`), so XLA
    inserts the activation reductions for the row-split GEMMs;
  - raw serving leaves (embeddings, norms, MLA's wkv_b, the head) fall back
    to `param_spec` with fsdp off — "data" never appears on weights (every
    shard needs the full model to decode its own slots).
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_REPLICATED_TOKENS = ("router", "norm", "/n1", "/n2", "/nx", "gn", "mu",
                      "lam", "bias", "/ba", "/bx", "conv_b")


def _div(dim: int, size: int) -> bool:
    return size > 0 and dim % size == 0


def param_spec(path: str, shape: tuple[int, ...], *, model: int, data: int,
               fsdp: bool) -> P:
    """PartitionSpec for one parameter leaf addressed by `path`."""
    axes: list = [None] * len(shape)
    if len(shape) <= 1 or any(t in path for t in _REPLICATED_TOKENS):
        return P(*axes)

    if len(shape) == 4:
        # stacked expert weights (L, E, f, d): experts -> model (EP)
        if _div(shape[1], model):
            axes[1] = "model"
        if fsdp and _div(shape[3], data):
            axes[3] = "data"
        return P(*axes)

    # trailing (out, in) matrix; leading stacked axis (if 3D) stays None
    o, i = len(shape) - 2, len(shape) - 1
    if _div(shape[o], model):
        axes[o] = "model"
    elif _div(shape[i], model):
        axes[i] = "model"
    if fsdp and axes[i] is None and _div(shape[i], data):
        axes[i] = "data"
    return P(*axes)


def cache_spec(kind: str, shape: tuple[int, ...], *, model: int, data: int) -> P:
    """Decode-cache leaf spec. Layout convention: (L, B, S?, ..., feature).

    Batch (axis 1) -> data; the trailing feature axis -> model when the leaf
    is wide enough to matter (>= 3 trailing dims, e.g. (B, S, KV, hd) K/V or
    (B, H, d, d) WKV state); sequence/position axes stay unsharded.
    """
    axes: list = [None] * len(shape)
    if len(shape) >= 2 and _div(shape[1], data):
        axes[1] = "data"
    if len(shape) >= 4 and _div(shape[-1], model):
        axes[-1] = "model"
    return P(*axes)


# --------------------------------------------------------------------------
# tree-level builders (used by launch/dryrun and the distributed examples)
# --------------------------------------------------------------------------

def _mesh_sizes(mesh) -> tuple[int, int]:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("model", 1), sizes.get("data", 1)


def _path_str(path) -> str:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k)))))
    return "/".join(out)


def state_shardings(state, mesh, *, fsdp: bool):
    """NamedShardings for a params-or-train-state pytree (shape-structs ok)."""
    model, data = _mesh_sizes(mesh)

    def one(path, leaf):
        spec = param_spec(_path_str(path), tuple(leaf.shape),
                          model=model, data=data, fsdp=fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, state)


def cache_shardings(cache, mesh):
    model, data = _mesh_sizes(mesh)

    def one(path, leaf):
        p = _path_str(path)
        kind = p.rsplit("/", 1)[-1]
        spec = cache_spec(kind, tuple(leaf.shape), model=model, data=data)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, cache)


def input_shardings(batch, mesh):
    """Token/label/embed inputs: batch axis -> data, rest replicated."""
    _, data = _mesh_sizes(mesh)

    def one(leaf):
        axes: list = [None] * len(leaf.shape)
        if len(leaf.shape) >= 1 and _div(leaf.shape[0], data):
            axes[0] = "data"
        return NamedSharding(mesh, P(*axes))

    return jax.tree.map(one, batch)


# --------------------------------------------------------------------------
# serving (mesh-sharded ServeEngine: slot-affine pool over "data", packed
# weights over "model" — see the module docstring and serve/README.md)
# --------------------------------------------------------------------------

# every serving-cache leaf — (stack, n_blocks, block, ...) token pools,
# (stack, n_slots, ...) recurrent state, (stack, n_slots, max_len, ...) dense
# caches — splits its axis-1 slot/block home over "data"; usable directly as
# the shard_map in/out spec prefix for the whole cache pytree
SERVE_CACHE_SPEC = P(None, "data")


def packed_weight_spec(shape: tuple[int, ...], *, model: int) -> P:
    """Spec for one field of a PackedQWeight: `packed` (..., N, K/2) and
    `scales8` (..., N, K/16) shard the out-feature axis N over "model"
    (group boundaries along K stay device-local by construction); the
    per-matrix `gscale` (...,) replicates. Leading stacked layer/expert axes
    are never sharded, mirroring `param_spec`."""
    axes: list = [None] * len(shape)
    if len(shape) >= 2 and _div(shape[-2], model):
        axes[-2] = "model"
    return P(*axes)


def serve_param_shardings(params, mesh):
    """NamedShardings for a serving params pytree (prequantized or raw).

    PackedQWeight leaves use `packed_weight_spec`; raw leaves use the
    training `param_spec` with fsdp off, so only "model" is ever assigned —
    inside the serving shard_map "data" is a MANUAL axis over decode slots
    and weights must be replicated across it. Works on concrete arrays and
    on eval_shape structs (dry-run lowering)."""
    from repro.core.linear import PackedQWeight
    model, _ = _mesh_sizes(mesh)

    def one(path, leaf):
        if isinstance(leaf, PackedQWeight):
            return PackedQWeight(
                *(NamedSharding(mesh, packed_weight_spec(tuple(f.shape),
                                                         model=model))
                  for f in leaf))
        spec = param_spec(_path_str(path), tuple(leaf.shape),
                          model=model, data=1, fsdp=False)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(
        one, params, is_leaf=lambda x: isinstance(x, PackedQWeight))


def serve_cache_shardings(cache, mesh):
    """NamedShardings placing every serving-cache leaf on SERVE_CACHE_SPEC."""
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, SERVE_CACHE_SPEC), cache)
