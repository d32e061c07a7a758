"""Weights of a serving configuration that names a (data, model) mesh are
made on their shards: each leaf carries the sharding the program's
`serve_param_shardings` gives it, each device holds its share of the GEMM
weights, and the values are those made without a mesh for the same seed.
Four CPU devices stand for a four-chip host (conftest.py)."""

from collections import defaultdict

import jax
import numpy as np
import pytest

from bench.lib import cell as C
from bench.lib import serve
from bench.tests import tiny

SEED = 2**31 + 3
MESH = {"data": 2, "model": 2}


@pytest.fixture(scope="module")
def made():
    """(mesh, weights made on the mesh, weights made on one device)."""
    from repro.launch.mesh import make_serve_mesh
    c = tiny.cell("granite-8b-bf16.chat", mesh=MESH)
    m, e = c.config["model"], c.config["engine"]
    weights = C.module(c.config["weights"])
    mesh = make_serve_mesh(MESH["data"], MESH["model"])
    return (mesh, serve.serving_params(m, SEED, e["scheme"], weights, mesh),
            serve.serving_params(m, SEED, e["scheme"], weights))


def _leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


def test_weights_carry_the_programs_shardings(made):
    from repro.dist import sharding as SH
    mesh, params, _ = made
    want = SH.serve_param_shardings(params, mesh)
    for (path, x), s in zip(_leaves(params), jax.tree.leaves(want)):
        assert x.sharding.is_equivalent_to(s, x.ndim), \
            jax.tree_util.keystr(path)


def test_each_device_holds_its_share_of_the_gemm_weights(made):
    """Every GEMM weight (all but the norm gains) splits evenly over
    "model" at the tiny widths: each device holds half of their bytes."""
    mesh, params, _ = made
    gemm = [x for path, x in _leaves(params)
            if jax.tree_util.keystr(path[-1:]) != "['g']"]
    held = defaultdict(int)
    for x in gemm:
        for shard in x.addressable_shards:
            held[shard.device] += shard.data.nbytes
    assert set(held) == set(mesh.devices.flat)
    total = sum(x.nbytes for x in gemm)
    assert all(b * MESH["model"] == total for b in held.values()), held


def test_weights_equal_those_made_on_one_device(made):
    _, sharded, whole = made
    for (path, a), b in zip(_leaves(sharded), jax.tree.leaves(whole)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), \
            jax.tree_util.keystr(path)
