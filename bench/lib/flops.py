"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that no program change can move them. `m` is a
configuration's "model" dict (bench/configs/*.json).
"""

from __future__ import annotations

BF16 = 2
F32 = 4


# d x d_ff matrices of each MLP kind the program has (models/blocks.py):
# SwiGLU's gate, up and down; the squared-ReLU and GELU MLPs' up and down
MLP_MATRICES = {"swiglu": 3, "relu2": 2, "gelu": 2}


def layer_params(m: dict) -> int:
    """Weights of one GQA layer that go through a GEMM: the four attention
    projections and the MLP's matrices. A model with no `mlp` key has the
    program's default, SwiGLU; a kind not in MLP_MATRICES is refused."""
    d, hd = m["d_model"], m["head_dim"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    kind = m.get("mlp", "swiglu")
    if kind not in MLP_MATRICES:
        raise ValueError(f"no GEMM count for mlp {kind!r}; "
                         f"have {sorted(MLP_MATRICES)}")
    return d * q + 2 * d * kv + q * d + MLP_MATRICES[kind] * d * m["d_ff"]


def gemm_params(m: dict) -> int:
    """Non-embedding parameters plus the LM head."""
    return m["n_layers"] * layer_params(m) + m["vocab"] * m["d_model"]


def serve_token_flops(m: dict, ctx: int) -> int:
    """Forward FLOPs of one token that attends over `ctx` positions."""
    attn = 4 * m["n_heads"] * m["head_dim"] * ctx * m["n_layers"]
    return 2 * gemm_params(m) + attn


def train_token_flops(m: dict, seq: int) -> int:
    """Forward + backward FLOPs per token of causal training at `seq`:
    6 x (GEMM params) + 6 x seq x d x layers for attention (QK^T and PV,
    2 x seq x d each over a causal half, times 3 for the backward)."""
    return 6 * gemm_params(m) + 6 * seq * m["n_heads"] * m["head_dim"] * \
        m["n_layers"]


def forward_flops(m: dict, size: int, pos, active) -> int:
    """FLOPs of the tokens one engine forward computes for its active rows
    (padding rows excluded): row b's queries sit at pos[b] .. pos[b]+size-1."""
    total = 0
    for p, a in zip(pos, active):
        if a:
            total += sum(serve_token_flops(m, int(p) + s + 1)
                         for s in range(size))
    return total


def paged_attention_work(m: dict, size: int, pos, active,
                         kv_bytes: int = BF16) -> tuple[int, int]:
    """(FLOPs, bytes) of the paged-attention calls of one forward, all
    layers: keys and values at each active row's context length in the
    pool's storage format, q in bf16 and the float32 output; per query at
    position p, 4 x heads x head_dim x (p + 1) FLOPs."""
    h, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    flops = nbytes = 0
    for p, a in zip(pos, active):
        if not a:
            continue
        ctx = int(p) + size
        flops += sum(4 * h * hd * (int(p) + s + 1) for s in range(size))
        nbytes += 2 * ctx * kv * hd * kv_bytes + size * h * hd * (BF16 + F32)
    return flops * m["n_layers"], nbytes * m["n_layers"]
