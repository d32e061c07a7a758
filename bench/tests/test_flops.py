"""FLOP and byte counts against values worked out by hand at one shape."""

import json
from pathlib import Path

import pytest

from bench.lib import flops

ROOT = Path(__file__).resolve().parents[2]

M = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 16, "d_ff": 128, "vocab": 100}


def test_layer_and_gemm_params():
    # wq 64x64 + wk 64x32 + wv 64x32 + wo 64x64 + 3 x 64x128
    assert flops.layer_params(M) == 4096 + 2048 + 2048 + 4096 + 24576
    assert flops.gemm_params(M) == 2 * 36864 + 6400


@pytest.mark.parametrize("mlp,matrices", [
    (None, 3), ("swiglu", 3), ("relu2", 2), ("gelu", 2)])
def test_layer_params_by_mlp(mlp, matrices):
    """SwiGLU (the program's default) has three d x d_ff matrices, the
    squared-ReLU and GELU MLPs two."""
    m = M if mlp is None else dict(M, mlp=mlp)
    assert flops.layer_params(m) == 4096 + 2048 + 2048 + 4096 + \
        matrices * 64 * 128


def test_layer_params_unknown_mlp_raises():
    with pytest.raises(ValueError, match="geglu"):
        flops.layer_params(dict(M, mlp="geglu"))


@pytest.mark.parametrize("config,want", [
    # wq, wo 4096^2 + wk, wv 4096 x 1024 + 3 x 4096 x 14336
    ("granite-8b-bf16", 2 * 4096**2 + 2 * 4096 * 1024 + 3 * 4096 * 14336),
    # wq, wo 4096^2 + wk, wv 4096 x 512 + 3 x 4096 x 11008
    ("yi-9b-train", 2 * 4096**2 + 2 * 4096 * 512 + 3 * 4096 * 11008)])
def test_layer_params_of_the_configurations(config, want):
    m = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                   .read_text())["model"]
    assert flops.layer_params(m) == want


def test_serve_token_flops():
    # 2 x 80128 + 4 heads x 16 x ctx 10 x 4 FLOPs x 2 layers
    assert flops.serve_token_flops(M, 10) == 2 * 80128 + 4 * 4 * 16 * 10 * 2


def test_train_token_flops_matches_yi_9b_reckoning():
    yi = {"n_layers": 2, "d_model": 4096, "n_heads": 32, "n_kv_heads": 4,
          "head_dim": 128, "d_ff": 11008, "vocab": 8000}
    per_tok = flops.train_token_flops(yi, 4096)
    assert per_tok == 6 * (2 * 173015040 + 8000 * 4096) + \
        6 * 4096 * 4096 * 2
    assert abs(per_tok / 2.47e9 - 1) < 0.01


def test_forward_flops_counts_active_rows_only():
    f = flops.forward_flops(M, 2, [3, 0, 7], [True, False, True])
    want = sum(flops.serve_token_flops(M, c) for c in (4, 5, 8, 9))
    assert f == want


def test_paged_attention_work():
    fl, nb = flops.paged_attention_work(M, 1, [9, 5], [True, False])
    # one query at position 9: context 10; 4 x 4 x 16 x 10 FLOPs per layer
    assert fl == 4 * 4 * 16 * 10 * 2
    # K and V: 10 tokens x 2 heads x 16 x bf16 x 2; q bf16 + out f32
    assert nb == (2 * 10 * 2 * 16 * 2 + 1 * 4 * 16 * 6) * 2
